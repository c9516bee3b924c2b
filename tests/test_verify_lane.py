"""Ingest-side signature-verification lane (round 13).

End-to-end through AggregatorSink: extraction → classification →
batched device ECDSA + pure-python host fallback → per-issuer fold,
under both the serial per-chunk dispatch and the staged device queue,
with verdict truth recomputed independently per lane. Budget
discipline: device batches pad to width 32 (the compile the ECDSA
parity suite already paid), ONE serial sink run is shared module-wide
by every read-side assertion (checkpoint / issuer meta / reports),
and the walker compiles reuse one batch shape.
"""

import base64
import datetime
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ct_mapreduce_tpu.agg.aggregator import (  # noqa: E402
    HostSnapshotAggregator,
    TpuAggregator,
)
from ct_mapreduce_tpu.ingest import leaf as leaflib  # noqa: E402
from ct_mapreduce_tpu.ingest.sync import AggregatorSink, RawBatch  # noqa: E402
from ct_mapreduce_tpu.utils import minicert  # noqa: E402
from ct_mapreduce_tpu.verify import host, sct as sctlib  # noqa: E402
from ct_mapreduce_tpu.verify.lane import (  # noqa: E402
    LogKeyRegistry,
    SignatureVerifier,
    resolve_verify,
)

FUTURE = datetime.datetime(2031, 6, 15, tzinfo=datetime.timezone.utc)


def _signers():
    return (sctlib.EcSctSigner("vl-a"),
            sctlib.EcSctSigner("vl-b", host.P384),
            sctlib.RsaSctSigner())


def _corpus(n=24):
    """[(leaf_der, issuer_der)] + expected outcome totals."""
    issuer = minicert.make_cert(serial=1, issuer_cn="Verify CA",
                                is_ca=True, not_after=FUTURE)
    p256, p384, rsa = _signers()
    unknown = sctlib.EcSctSigner("vl-unknown")
    pairs, expect = [], dict(verified=0, failed=0, no_sct=0, no_key=0,
                             host=0, device=0, p384=0)
    for s in range(n):
        base = minicert.make_cert(
            serial=1000 + s, issuer_cn="Verify CA", subject_cn=f"l{s}",
            is_ca=False, not_after=FUTURE)
        kind = s % 6
        if kind == 0:
            der = sctlib.attach_sct(base, p256, 10**12 + s,
                                    issuer_der=issuer)
            expect["verified"] += 1
            expect["device"] += 1
        elif kind == 1:
            der = sctlib.attach_sct(base, p256, 10**12 + s,
                                    corrupt_signature=True,
                                    issuer_der=issuer)
            expect["failed"] += 1
            expect["device"] += 1
        elif kind == 2:
            # P-384 lanes ride the DEVICE since round 17 (re-extracted
            # from row bytes, verified by the windowed P-384 kernel).
            der = sctlib.attach_sct(base, p384, 10**12 + s,
                                    issuer_der=issuer)
            expect["verified"] += 1
            expect["device"] += 1
            expect["p384"] += 1
        elif kind == 3:
            der = sctlib.attach_sct(base, rsa, 10**12 + s,
                                    corrupt_signature=True,
                                    issuer_der=issuer)
            expect["failed"] += 1
            expect["host"] += 1
        elif kind == 4:
            der = base
            expect["no_sct"] += 1
        else:
            der = sctlib.attach_sct(base, unknown, 10**12 + s,
                                    issuer_der=issuer)
            expect["no_key"] += 1
        pairs.append((der, issuer))
    return pairs, expect


def _wire(pairs):
    lis = [base64.b64encode(leaflib.encode_leaf_input(
        leaf, timestamp_ms=1_700_000_000_000 + j)).decode()
        for j, (leaf, _) in enumerate(pairs)]
    eds = [base64.b64encode(leaflib.encode_extra_data([iss])).decode()
           for _, iss in pairs]
    return lis, eds


def _run_sink(pairs, flush=16):
    agg = TpuAggregator(capacity=1 << 12, batch_size=flush)
    sink = AggregatorSink(agg, flush_size=flush, device_queue_depth=0,
                          verify_signatures=True)
    sink.verifier.batch_width = 32  # the parity suite's compiled width
    for s in _signers():
        sink.verifier.keys.register_signer(s)
    lis, eds = _wire(pairs)
    sink.store_raw_batch(RawBatch(lis, eds, 0, "v-log"))
    sink.flush()
    return agg, sink


@pytest.fixture(scope="module")
def serial_run():
    """One serial-dispatch sink run, shared by every read-side test."""
    pairs, expect = _corpus()
    agg, sink = _run_sink(pairs)
    return pairs, expect, agg, sink


def _check_outcomes(agg, sink, expect, n_pairs):
    st = sink.verifier.stats
    assert st["verified"] == expect["verified"]
    assert st["failed"] == expect["failed"]
    assert st["no_sct"] == expect["no_sct"]
    assert st["no_key"] == expect["no_key"]
    assert st["host_lanes"] == expect["host"]
    assert st["device_lanes"] == expect["device"]
    assert st["p384_lanes"] == expect["p384"]
    # Q-table accounting: one lookup per device lane, one miss per
    # distinct (log key, registry epoch) — steady state is all hits.
    assert st["qtable_hits"] + st["qtable_misses"] == expect["device"]
    assert st["qtable_misses"] == 2  # one P-256 key + one P-384 key
    vc = agg.verify_counts()
    assert sum(v for v, _ in vc.values()) == expect["verified"]
    assert sum(f for _, f in vc.values()) == expect["failed"]
    # The dedup side is untouched by the lane: every lane still counts.
    assert agg.metrics["inserted"] == n_pairs


def test_sink_lane_outcomes_serial(serial_run):
    pairs, expect, agg, sink = serial_run
    _check_outcomes(agg, sink, expect, len(pairs))


def test_device_verify_spans_cover_every_device_lane():
    """The kernels really ran, and in batches: the ``device.verify``
    spans of a traced run carry exactly the device-decidable lanes,
    more than one to an execution, and each curve's Q-table holds its
    one log key."""
    from ct_mapreduce_tpu.telemetry import trace

    pairs, expect = _corpus()
    tracer = trace.enable()
    t0 = tracer.now_us()
    try:
        _agg, sink = _run_sink(pairs)
        spans = [e for e in tracer.events()
                 if e.get("ph") == "X" and e["name"] == "device.verify"
                 and e["ts"] >= t0]
    finally:
        trace.disable()
    lanes = sum(int(e["args"]["lanes"]) for e in spans)
    assert spans and lanes == expect["device"]
    assert lanes / len(spans) > 1.0
    qtable = sink.verifier.health()["qtable"]
    assert qtable["p256"]["occupancy"] == 1
    assert qtable["p384"]["occupancy"] == 1


def test_lane_python_extraction_parity(serial_run, monkeypatch):
    """CTMR_NATIVE=0 (pure-python decode AND extraction) produces the
    exact same verify outcomes — the degradation contract end to end."""
    pairs, expect, _agg, native_sink = serial_run
    monkeypatch.setenv("CTMR_NATIVE", "0")
    agg, sink = _run_sink(pairs)
    assert sink.verifier.stats == native_sink.verifier.stats


def test_verify_off_means_no_verifier():
    agg = TpuAggregator(capacity=1 << 12, batch_size=16)
    sink = AggregatorSink(agg, flush_size=16, device_queue_depth=0)
    assert sink.verifier is None
    assert not agg.verify_counts()
    assert not agg.drain().verified


def test_checkpoint_roundtrip(serial_run, tmp_path):
    _pairs, expect, agg, _sink = serial_run
    path = str(tmp_path / "agg.npz")
    agg.save_checkpoint(path)
    h = HostSnapshotAggregator(capacity=1 << 10)
    h.load_checkpoint(path)
    assert np.array_equal(h.verify_verified, agg.verify_verified)
    snap = h.drain()
    assert sum(snap.verified.values()) == expect["verified"]
    assert sum(snap.failed.values()) == expect["failed"]
    # Pre-round-13 snapshots (no verify arrays) load as zeros.
    z = dict(np.load(path, allow_pickle=True))
    z.pop("verify_verified")
    z.pop("verify_failed")
    legacy = str(tmp_path / "legacy.npz")
    with open(legacy, "wb") as fh:
        np.savez_compressed(fh, **z)
    h2 = HostSnapshotAggregator(capacity=1 << 10)
    h2.load_checkpoint(legacy)
    assert not h2.verify_counts()
    assert not h2.drain().verified


def test_issuer_meta_carries_verify_counts(serial_run):
    from ct_mapreduce_tpu.serve.server import MembershipOracle

    _pairs, expect, agg, _sink = serial_run
    oracle = MembershipOracle(agg, replicas=1, device=False,
                              cache_size=-1)
    try:
        iss_id = next(iter(agg.verify_counts()))
        meta = oracle.issuer_meta(iss_id)
        assert meta["verified"] == expect["verified"]
        assert meta["failed"] == expect["failed"]
    finally:
        oracle.close()


def test_storage_statistics_verify_totals(serial_run, tmp_path):
    import io
    import json

    from ct_mapreduce_tpu.cmd import storage_statistics as stats
    from ct_mapreduce_tpu.config import CTConfig

    _pairs, expect, agg, _sink = serial_run
    path = str(tmp_path / "agg.npz")
    agg.save_checkpoint(path)
    cfg = CTConfig()
    cfg.backend = "tpu"
    cfg.agg_state_path = path
    out = io.StringIO()
    assert stats.report_from_tpu_snapshot(cfg, out) == 0
    text = out.getvalue()
    assert f"{expect['verified']} scts verified" in text
    assert f"{expect['failed']} scts failed" in text
    report = stats.collect_tpu_report(cfg)
    assert report["totals"]["sctsVerified"] == expect["verified"]
    assert report["totals"]["sctsFailed"] == expect["failed"]
    json.dumps(report)  # stays serializable


def test_resolve_verify_env_layering(monkeypatch):
    for var in ("CTMR_VERIFY", "CTMR_VERIFY_KEYS", "CTMR_VERIFY_BATCH",
                "CTMR_VERIFY_PRECOMP_WINDOW", "CTMR_VERIFY_QTABLE_SIZE"):
        monkeypatch.delenv(var, raising=False)
    assert resolve_verify() == (False, "", 1024, 8, 32)
    monkeypatch.setenv("CTMR_VERIFY", "1")
    monkeypatch.setenv("CTMR_VERIFY_KEYS", "/tmp/k.json")
    monkeypatch.setenv("CTMR_VERIFY_BATCH", "256")
    monkeypatch.setenv("CTMR_VERIFY_PRECOMP_WINDOW", "4")
    monkeypatch.setenv("CTMR_VERIFY_QTABLE_SIZE", "7")
    assert resolve_verify() == (True, "/tmp/k.json", 256, 4, 7)
    # explicit beats env; junk batch env is ignored
    monkeypatch.setenv("CTMR_VERIFY_BATCH", "zap")
    assert resolve_verify(False, "x.json", 64, 2, 3) \
        == (False, "x.json", 64, 2, 3)
    assert resolve_verify(True) == (True, "/tmp/k.json", 1024, 4, 7)
    # explicit window 0 (the legacy ladder) beats a set env var —
    # 0 is a REAL value, the parity fallback.
    assert resolve_verify(True, window=0)[3] == 0
    # invalid windows (must divide 16) fall back to the default 8.
    monkeypatch.setenv("CTMR_VERIFY_PRECOMP_WINDOW", "5")
    assert resolve_verify(True)[3] == 8
    monkeypatch.setenv("CTMR_VERIFY_QTABLE_SIZE", "junk")
    assert resolve_verify(True)[4] == 32


def test_sink_loads_keys_from_file(tmp_path):
    reg = LogKeyRegistry()
    p256, p384, rsa = _signers()
    for s in (p256, p384, rsa):
        reg.register_signer(s)
    keys_path = tmp_path / "keys.json"
    keys_path.write_text(reg.to_json())
    agg = TpuAggregator(capacity=1 << 12, batch_size=16)
    sink = AggregatorSink(agg, flush_size=16, device_queue_depth=0,
                          verify_signatures=True,
                          verify_log_keys=str(keys_path))
    assert isinstance(sink.verifier, SignatureVerifier)
    assert len(sink.verifier.keys) == 3
    assert sink.verifier.keys.is_p256(p256.log_id)


# -- round 17: Q-table cache, routing, legacy-window parity --------------

def _sct_rows(certs):
    pad = max(len(c) for c in certs) + 16
    data = np.zeros((len(certs), pad), np.uint8)
    length = np.zeros((len(certs),), np.int32)
    for i, c in enumerate(certs):
        data[i, : len(c)] = np.frombuffer(c, np.uint8)
        length[i] = len(c)
    return data, length


def _submit(verifier, certs):
    data, length = _sct_rows(certs)
    scts = sctlib.extract_scts_np(data, length)
    verifier.submit_chunk(
        scts, np.zeros((len(certs),), np.int64),
        np.ones((len(certs),), bool), data, length)


def _sct_cert(signer, serial, ts=10**12):
    base = minicert.make_cert(serial=serial, issuer_cn="QT CA",
                              subject_cn=f"qt{serial}", is_ca=False,
                              not_after=FUTURE)
    return sctlib.attach_sct(base, signer, ts)


def test_qtable_lru_eviction_and_epoch_invalidation():
    """The per-log-key Q-table LRU: one miss per distinct (key,
    registry epoch), hits afterwards, eviction under a 1-slot cap,
    and re-registration (epoch bump) invalidating exactly that key.
    Width 32 — the compile the parity suite already paid."""
    ka, kb = sctlib.EcSctSigner("qt-a"), sctlib.EcSctSigner("qt-b")
    ca, cb = _sct_cert(ka, 1), _sct_cert(kb, 2)

    agg = TpuAggregator(capacity=1 << 12, batch_size=16)
    tight = SignatureVerifier(agg, batch_width=32, qtable_size=1)
    for s in (ka, kb):
        tight.keys.register_signer(s)
    _submit(tight, [ca, cb])
    tight.drain()
    st = tight.stats
    assert (st["qtable_misses"], st["qtable_hits"]) == (2, 0)
    _submit(tight, [ca])  # a was evicted by b under the 1-slot cap
    tight.drain()
    assert (st["qtable_misses"], st["qtable_hits"]) == (3, 0)
    assert tight.health()["qtable"]["p256"]["occupancy"] == 1
    assert st["verified"] == 3 and st["failed"] == 0

    roomy = SignatureVerifier(agg, batch_width=32, qtable_size=4)
    for s in (ka, kb):
        roomy.keys.register_signer(s)
    _submit(roomy, [ca, cb])
    roomy.drain()
    _submit(roomy, [ca, cb])  # steady state: 100% hits
    roomy.drain()
    st = roomy.stats
    assert (st["qtable_misses"], st["qtable_hits"]) == (2, 2)
    # Epoch bump: re-registering ka invalidates ONLY ka's slot.
    roomy.keys.register_signer(ka)
    _submit(roomy, [ca, cb])
    roomy.drain()
    assert (st["qtable_misses"], st["qtable_hits"]) == (3, 3)
    h = roomy.health()
    assert h["window"] == roomy.window > 0
    assert h["qtable"]["p256"]["capacity"] == 4
    assert h["qtable"]["p256"]["occupancy"] == 3  # stale ka slot + 2
    assert h["stats"]["verified"] == st["verified"] == 6


def test_p384_host_fallback_routing():
    """The third routing leg: a lane keyed to a P-384 entry whose SCT
    is NOT device-decidable (RSA algorithm bytes under the key's
    log id) replays through the host verifier and fails closed —
    P-256 device / P-384 device / host fallback all pinned."""
    rsa = sctlib.RsaSctSigner()
    cert = _sct_cert(rsa, 3)
    p384k = sctlib.EcSctSigner("fb-384", host.P384)
    agg = TpuAggregator(capacity=1 << 12, batch_size=16)
    v = SignatureVerifier(agg, batch_width=32)
    v.keys.register({
        "log_id": rsa.log_id.hex(), "alg": "p384",
        "x": hex(p384k.q[0]), "y": hex(p384k.q[1]),
    })
    _submit(v, [cert])
    v.drain()
    st = v.stats
    assert st["host_lanes"] == 1 and st["device_lanes"] == 0
    assert st["p384_lanes"] == 0
    assert st["failed"] == 1 and st["verified"] == 0


def test_lane_window0_legacy_parity():
    """verifyPrecompWindow = 0 routes the lane down the round-13
    Jacobian ladder (the parity fallback) — same outcomes as the
    windowed default on the same lanes. P-256 only: the legacy P-384
    compile is slow-tier (test_ecdsa), and the lane shares kernels
    with it."""
    ka = sctlib.EcSctSigner("w0-a")
    certs = [_sct_cert(ka, 10), _sct_cert(ka, 11)]
    bad = sctlib.attach_sct(
        minicert.make_cert(serial=12, issuer_cn="QT CA",
                           subject_cn="qt12", is_ca=False,
                           not_after=FUTURE),
        ka, 10**12, corrupt_signature=True)
    certs.append(bad)

    outcomes = []
    for window in (0, None):
        agg = TpuAggregator(capacity=1 << 12, batch_size=16)
        v = SignatureVerifier(agg, batch_width=32, window=window)
        v.keys.register_signer(ka)
        _submit(v, certs)
        v.drain()
        outcomes.append((v.stats["verified"], v.stats["failed"],
                         v.stats["device_lanes"]))
    assert outcomes[0] == outcomes[1] == (2, 1, 3)
    # window 0 builds no tables: the Q-table stats stay zero.
    assert outcomes[0] == (2, 1, 3)
