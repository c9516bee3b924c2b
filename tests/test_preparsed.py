"""Pre-parsed ingest lane: sidecar extraction + walker-free device step.

Three contracts pinned here (ISSUE 7):

1. **Sidecar == device walker, on EVERY input.** The native extractor
   (ctmr_extract_sidecars) is a scalar port of ops/der_kernel.py's
   parse_certs — bit-exact ok bits and fields across the mutation
   fuzz, walker-rejected mutants included. This is what lets the
   pre-parsed lane substitute host extraction for the on-device walk
   without re-routing a single lane (the ParsEval divergence class,
   arXiv:2405.18993, as a hard test instead of a hope).
2. **Sidecar fields == exact host lane** on certs both accept (the
   same hard contract the walker itself carries in
   test_der_kernel.py's fuzz — serial window, expiry bucket, CA flag,
   CN bytes, CRLDP URLs).
3. **Undecidable lanes fall back to the device walker** through the
   sink, with aggregate results AND host-lane spill counts identical
   to the pure walker lane.
"""

import base64
import datetime
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ct_mapreduce_tpu.core import der as hostder
from ct_mapreduce_tpu.native import available, leafpack
from ct_mapreduce_tpu.ops import der_kernel

from tests import certgen
from tests.test_der_kernel import fixture_certs, pack

UTC = datetime.timezone.utc
FUTURE = datetime.datetime(2031, 6, 15, tzinfo=UTC)

pytestmark = pytest.mark.skipif(
    not available(), reason="native library unavailable (no C++ compiler)")

# (sidecar field, ParsedCerts field) — everything the walker extracts.
FIELD_PAIRS = [
    ("serial_off", "serial_off"), ("serial_len", "serial_len"),
    ("not_after_hour", "not_after_hour"), ("is_ca", "is_ca"),
    ("has_crldp", "has_crldp"),
    ("cn_off", "issuer_cn_off"), ("cn_len", "issuer_cn_len"),
    ("issuer_off", "issuer_off"), ("issuer_len", "issuer_len"),
    ("spki_off", "spki_off"), ("spki_len", "spki_len"),
    ("crldp_off", "crldp_off"), ("crldp_len", "crldp_len"),
]


def _assert_sidecar_equals_walker(ders, pad_to=1024):
    data, length = pack(ders, pad_to=pad_to)
    sc = leafpack.extract_sidecars(data, length)
    out = der_kernel.parse_certs(data, length)
    ok_dev = np.asarray(out.ok)
    assert np.array_equal(sc.ok.astype(bool), ok_dev), (
        "ok-bit divergence at lanes "
        f"{np.nonzero(sc.ok.astype(bool) != ok_dev)[0][:10]}")
    for i in np.nonzero(ok_dev)[0]:
        for sf, df in FIELD_PAIRS:
            got = int(getattr(sc, sf)[i])
            want = int(np.asarray(getattr(out, df))[i])
            assert got == want, (
                f"lane {i} field {sf}: sidecar={got} walker={want} "
                f"der={ders[i].hex()}")
    return sc, out


def test_sidecar_matches_walker_on_fixtures():
    certs = fixture_certs() + [
        certgen.make_cert(serial=7, crl_dps=("ldap://drop.me/x",)),
        certgen.make_cert(serial=8, is_ca=True),
        certgen.make_cert(serial=9, extra_extensions=5),
    ]
    sc, _ = _assert_sidecar_equals_walker(certs)
    assert sc.ok.all()


@pytest.mark.slow
def test_sidecar_matches_walker_on_mutation_fuzz():
    """The strong pin: ok bits AND fields bit-equal on 400 mutants —
    including the walker-REJECTED ones (equality of the reject set is
    what guarantees identical host-lane spill counts).

    @slow since round 15 (tier-1 budget banking, ISSUE 10): the ok-bit
    agreement is now ALSO pinned tier-1 by the divergence-classified
    walker fuzz (test_der_kernel.py: sidecar_undecidable == 0 over 300
    mutants) and the field equality by
    test_sidecar_fields_match_exact_host_lane_fuzz below; this 400-
    mutant sweep re-walks the same contract and runs in the full
    (unmarked) suite."""
    rng = np.random.default_rng(20260804)
    bases = fixture_certs()
    mutants = []
    for _ in range(400):
        b = bytearray(bases[int(rng.integers(len(bases)))])
        for _k in range(int(rng.integers(1, 4))):
            b[int(rng.integers(len(b)))] ^= int(rng.integers(1, 256))
        mutants.append(bytes(b))
    sc, out = _assert_sidecar_equals_walker(mutants)
    accepted = int(np.asarray(out.ok).sum())
    rejected = len(mutants) - accepted
    # The fuzz must exercise both sides of the ok bit.
    assert accepted > 50 and rejected > 10, (accepted, rejected)


def test_sidecar_fields_match_exact_host_lane_fuzz():
    """Satellite contract: on every fuzzed DER that BOTH the sidecar
    extractor and the strict host parser accept, the identity-surface
    fields agree byte-for-byte (serial window, expiry bucket, isCA,
    CN bytes, CRLDP URLs). Walker-style bounded leniency (sidecar
    accepts, host rejects) is tolerated and bounded, exactly like the
    device walker's own fuzz contract."""
    rng = np.random.default_rng(20260805)
    bases = fixture_certs()
    mutants = []
    for _ in range(300):
        b = bytearray(bases[int(rng.integers(len(bases)))])
        b[int(rng.integers(len(b)))] ^= int(rng.integers(1, 256))
        mutants.append(bytes(b))
    data, length = pack(mutants, pad_to=1024)
    sc = leafpack.extract_sidecars(data, length)
    accepted = mismatches = host_rejects = 0
    for i, der in enumerate(mutants):
        if not sc.ok[i]:
            continue
        accepted += 1
        try:
            ref = hostder.parse_cert(der)
        except Exception:
            host_rejects += 1
            continue
        serial_window = der[int(sc.serial_off[i]):
                            int(sc.serial_off[i]) + int(sc.serial_len[i])]
        # A CN the scan leaves unsaid (length -1) is the host lane's to
        # read; one it states is Go's CommonName byte for byte.
        cn_bytes = (ref.issuer_cn_bytes if sc.cn_len[i] < 0 else
                    der[int(sc.cn_off[i]):int(sc.cn_off[i]) + int(sc.cn_len[i])])
        if bool(sc.has_crldp[i]):
            try:
                urls = hostder._parse_crldp(der, int(sc.crldp_off[i]))
            except Exception:
                urls = ["<unparseable>"]
        else:
            urls = []
        if (serial_window != ref.serial
                or int(sc.not_after_hour[i]) != ref.not_after_unix_hour
                or bool(sc.is_ca[i]) != ref.is_ca
                or cn_bytes != ref.issuer_cn_bytes
                or int(sc.spki_off[i]) != ref.spki_off
                or int(sc.spki_len[i]) != ref.spki_len
                or sorted(urls) != sorted(ref.crl_distribution_points)):
            mismatches += 1
            print(f"MISMATCH lane {i} der={der.hex()}")
    assert accepted > 50, accepted
    assert mismatches == 0, f"{mismatches}/{accepted}"
    assert host_rejects < 0.25 * accepted, (host_rejects, accepted)


def _wire(pairs):
    """[(leaf_der, issuer_der)] → base64 wire lists."""
    from ct_mapreduce_tpu.ingest import leaf as leaflib

    lis, eds = [], []
    for j, (leaf, issuer) in enumerate(pairs):
        lis.append(base64.b64encode(
            leaflib.encode_leaf_input(leaf, timestamp_ms=1700000000000 + j)
        ).decode())
        eds.append(base64.b64encode(
            leaflib.encode_extra_data([issuer])).decode())
    return lis, eds


def _replay_sink(lis, eds, preparsed, cn_prefixes=(), chunk=None,
                 now=None):
    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
    from ct_mapreduce_tpu.ingest.sync import AggregatorSink, RawBatch

    chunk = chunk or len(lis)
    agg = TpuAggregator(capacity=1 << 12, batch_size=chunk,
                        cn_prefixes=cn_prefixes, now=now)
    sink = AggregatorSink(agg, flush_size=chunk, device_queue_depth=0,
                          preparsed=preparsed)
    sink.store_raw_batch(RawBatch(list(lis), list(eds), 0, "pre-log"))
    sink.flush()
    return agg, agg.drain()


def test_undecidable_lanes_fall_back_to_device_walker():
    """Certs the walker cannot decide (here: past the MAX_EXTS scan
    budget, which the strict host parser handles fine) must flow
    through the sink's walker-fallback replay and land EXACTLY where
    the pure walker lane puts them — same drains, same metrics, same
    host-lane spill counts."""
    issuer = certgen.make_cert(serial=1, issuer_cn="Fallback CA",
                               is_ca=True, not_after=FUTURE)
    pairs = []
    for s in range(6):
        pairs.append((certgen.make_cert(
            serial=100 + s, issuer_cn="Fallback CA", is_ca=False,
            not_after=FUTURE), issuer))
    # Over-budget extension lists: walker (and sidecar) reject, exact
    # host lane accepts.
    heavy = [certgen.make_cert(serial=200 + s, issuer_cn="Fallback CA",
                               is_ca=False, not_after=FUTURE,
                               extra_extensions=der_kernel.MAX_EXTS + 4)
             for s in range(3)]
    pairs += [(h, issuer) for h in heavy]
    # And one structurally-broken cert (serial tag corrupted): both
    # lanes must hand it to the exact host lane, which rejects it.
    broken = bytearray(pairs[0][0])
    ref = hostder.parse_cert(bytes(broken))
    broken[ref.serial_off - 2] = 0x05
    pairs.append((bytes(broken), issuer))

    data, length = pack([p[0] for p in pairs], pad_to=2048)
    sc = leafpack.extract_sidecars(data, length)
    assert not sc.ok[6:].any(), "heavy/broken lanes must be undecidable"
    assert sc.ok[:6].all()

    lis, eds = _wire(pairs)
    agg_w, snap_w = _replay_sink(lis, eds, preparsed=False)
    agg_p, snap_p = _replay_sink(lis, eds, preparsed=True)
    assert snap_w.counts == snap_p.counts
    assert snap_w.crls == snap_p.crls and snap_w.dns == snap_p.dns
    assert agg_w.metrics == agg_p.metrics, (agg_w.metrics, agg_p.metrics)
    assert snap_p.total == 9  # 6 clean + 3 heavy; broken rejected
    assert agg_p.metrics["host_lane"] == 4  # 3 heavy + 1 broken
    assert agg_p.metrics["parse_errors"] == 1


def test_filter_routing_parity_with_walker_lane():
    """CA / expired / CN-filter / boundary-hour routing: the host-side
    predicate mirror must land every lane exactly where the walker
    lane lands it (metrics AND drained counts)."""
    now = datetime.datetime(2026, 1, 1, tzinfo=UTC)
    issuer = certgen.make_cert(serial=1, issuer_cn="Route CA", is_ca=True,
                               not_after=FUTURE)
    boundary = now.replace(minute=30)  # expires within the current hour
    # Before the factory's default not_before (2024), so give its own:
    # the `cryptography` builder refuses not_after < not_before.
    expired = now - datetime.timedelta(days=6 * 365)
    pairs = [
        (certgen.make_cert(serial=10, issuer_cn="Route CA", is_ca=False,
                           not_after=FUTURE), issuer),
        (certgen.make_cert(serial=11, issuer_cn="Route CA", is_ca=True,
                           not_after=FUTURE), issuer),  # filtered: CA
        (certgen.make_cert(serial=12, issuer_cn="Route CA", is_ca=False,
                           not_before=expired - datetime.timedelta(days=365),
                           not_after=expired), issuer),  # expired
        (certgen.make_cert(serial=13, issuer_cn="Route CA", is_ca=False,
                           not_after=boundary), issuer),  # boundary → host
        (certgen.make_cert(serial=14, issuer_cn="Other CA", is_ca=False,
                           not_after=FUTURE), issuer),  # CN filter miss
    ]
    lis, eds = _wire(pairs)
    results = []
    for pre in (False, True):
        agg, snap = _replay_sink(lis, eds, preparsed=pre,
                                 cn_prefixes=("Route CA",), now=now)
        results.append((agg.metrics, dict(snap.counts), snap.total))
    assert results[0] == results[1], results
    metrics, _counts, total = results[1]
    assert total == 2  # serial 10 (device) + serial 13 (boundary, host)
    assert metrics["filtered_ca"] == 1
    assert metrics["filtered_expired"] == 1
    assert metrics["filtered_cn"] == 1
    assert metrics["host_lane"] == 1


def test_preparsed_dedup_and_replay():
    """Dedup across the pre-parsed lane: a replayed stream inserts
    nothing, and the was-unknown bitmask decodes to the right lanes."""
    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
    from __graft_entry__ import _packed_batch, _NOW

    data, length, issuer_idx, valid, templates = _packed_batch(96, 1024)
    agg = TpuAggregator(capacity=1 << 12, batch_size=32, now=_NOW)
    for t in templates:
        agg.registry.get_or_assign(t.issuer_der)
    sc = leafpack.extract_sidecars(data, length)
    assert sc.ok.all()
    res1 = agg.ingest_preparsed(sc, issuer_idx, valid, data, length)
    assert res1.was_unknown.all()
    res2 = agg.ingest_preparsed(sc, issuer_idx, valid, data, length)
    assert not res2.was_unknown.any()
    assert agg.metrics["inserted"] == 96 and agg.metrics["known"] == 96
    assert agg.drain().total == 96


def test_preparsed_overflow_spills_to_host_lane_exactly():
    """Probe-overflow lanes surface through the compacted-flag
    readback (including the spill fallback past flag_cap) and resolve
    through the exact host lane — totals stay exact and match the
    walker lane at identical table settings."""
    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
    from ct_mapreduce_tpu.telemetry import metrics as tmetrics
    from __graft_entry__ import _packed_batch, _NOW

    n = 512
    data, length, issuer_idx, valid, templates = _packed_batch(n, 1024)
    sc = leafpack.extract_sidecars(data, length)

    def run(pre):
        # Tiny table, growth off, single probe: most lanes overflow.
        agg = TpuAggregator(capacity=32, batch_size=n, now=_NOW,
                            max_probes=1, grow_at=0, max_capacity=32)
        for t in templates:
            agg.registry.get_or_assign(t.issuer_der)
        if pre:
            res = agg.ingest_preparsed(sc, issuer_idx, valid, data, length)
        else:
            res = agg.ingest_packed(data, length, issuer_idx, valid)
        return agg, res

    sink = tmetrics.InMemSink()
    prev = tmetrics.get_sink()
    tmetrics.set_sink(sink)
    try:
        agg_p, res_p = run(True)
    finally:
        tmetrics.set_sink(prev)
    agg_w, res_w = run(False)
    assert agg_p.metrics["overflow"] > 64  # past flag_cap ⇒ spill path
    assert agg_p.metrics == agg_w.metrics
    assert np.array_equal(res_p.was_unknown, res_w.was_unknown)
    assert agg_p.drain().counts == agg_w.drain().counts
    counters = sink.snapshot()["counters"]
    assert counters.get("ingest.flag_cap_spill", 0) >= 1
    # The spill fetched the full overflow bitmask on top of the
    # compact block — still far below a per-lane int32 status row.
    assert counters["ingest.d2h_flag_bytes"] < 4 * n


def test_sidecar_unavailable_falls_back_to_walker_lane(monkeypatch):
    """CTMR_NATIVE=0 (or a missing library) must leave the sink on the
    walker lane — preparsed is an optimization, never a dependency."""
    monkeypatch.setenv("CTMR_NATIVE", "0")
    issuer = certgen.make_cert(serial=1, issuer_cn="NoNative CA",
                               is_ca=True, not_after=FUTURE)
    pairs = [(certgen.make_cert(serial=30 + s, issuer_cn="NoNative CA",
                                is_ca=False, not_after=FUTURE), issuer)
             for s in range(4)]
    lis, eds = _wire(pairs)
    agg, snap = _replay_sink(lis, eds, preparsed=True)
    assert snap.total == 4
    assert agg.metrics["inserted"] == 4
