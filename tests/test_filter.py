"""Filter emission (round 15): the crlite-style cascade artifact
compiled from the aggregation state.

Pins the acceptance contract of ISSUE 10:
- zero false negatives BY CONSTRUCTION over the full included set,
  fuzzed across bucket/open/sharded layouts and through table growth;
- artifact determinism (same state → identical bytes; ingest order
  and worker-local registry numbering cancel out), including the
  merged-fleet == serial-run byte identity;
- checkpoint interplay (emitFilter off leaves the .npz byte-identical
  and pre-round-15 snapshots load cleanly);
- the serve plane's filter-first → table-confirm tier staying
  parity-exact with the table-backed oracle under concurrent ingest,
  plus the /filter artifact-download routes and the ct-filter CLI.
"""

import io
import json
import os
import sys
import threading

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ct_mapreduce_tpu.agg.aggregator import (  # noqa: E402
    HostSnapshotAggregator,
    TpuAggregator,
)
from ct_mapreduce_tpu.core.types import ExpDate  # noqa: E402
from ct_mapreduce_tpu.filter import (  # noqa: E402
    FilterArtifact,
    FilterCascade,
    build_artifact,
    build_from_aggregator,
    canonical_keys,
    read_artifact,
    resolve_filter,
)
from ct_mapreduce_tpu.filter import cascade as cascade_mod  # noqa: E402
from ct_mapreduce_tpu.utils import minicert  # noqa: E402

ISSUER_DER = minicert.make_cert(serial=1, issuer_cn="Filter CA",
                                is_ca=True)
ISSUER_DER_B = minicert.make_cert(serial=2, issuer_cn="Filter CA B",
                                  is_ca=True)


def corpus(n=180, dupes=30, issuer_cn="Filter CA", issuer=ISSUER_DER,
           base=1000):
    entries = [
        (minicert.make_cert(serial=base + s, issuer_cn=issuer_cn,
                            subject_cn=f"f{s}.example"), issuer)
        for s in range(n)
    ]
    return entries + entries[:dupes]


def capture_identity_items(agg):
    """[(issuerID, expHour, serial)] for every captured serial."""
    items = []
    for (idx, eh), serials in agg.filter_capture.items():
        iss = agg.registry.issuer_at(idx).id()
        for sb in sorted(serials):
            items.append((iss, eh, sb))
    return items


# -- cascade primitive ----------------------------------------------------


def test_cascade_exact_over_disjoint_sets():
    rng = np.random.default_rng(2026)
    inc = rng.integers(0, 2**32, size=(400, 4), dtype=np.uint32)
    exc = rng.integers(0, 2**32, size=(3000, 4), dtype=np.uint32)
    c = FilterCascade.build(inc, exc, 0.01)
    assert c.contains(inc).all()
    assert not c.contains(exc).any()
    assert len(c.layers) >= 1
    assert c.bits_per_entry() < 64  # compact vs 128-bit fingerprints


def test_cascade_empty_edges():
    empty = np.zeros((0, 4), np.uint32)
    keys = np.arange(40, dtype=np.uint32).reshape(10, 4)
    # No included keys → no layers → everything answers excluded.
    c = FilterCascade.build(empty, keys, 0.01)
    assert not c.layers and not c.contains(keys).any()
    # No excluded universe → a single Bloom layer, still exact on
    # the included side.
    c = FilterCascade.build(keys, empty, 0.01)
    assert len(c.layers) == 1 and c.contains(keys).all()


def test_cascade_device_host_bit_parity():
    """The jitted scatter and the NumPy lane must produce bit-equal
    bitmaps — the device/host parity contract of the build."""
    rng = np.random.default_rng(7)
    inc = rng.integers(0, 2**32, size=(257, 4), dtype=np.uint32)
    exc = rng.integers(0, 2**32, size=(999, 4), dtype=np.uint32)
    host = FilterCascade.build(inc, exc, 0.02, use_device=False)
    dev = FilterCascade.build(inc, exc, 0.02, use_device=True)
    assert len(host.layers) == len(dev.layers)
    for a, b in zip(host.layers, dev.layers):
        assert (a.m, a.k) == (b.m, b.k)
        assert np.array_equal(a.words, b.words)


def test_cascade_env_disables_device(monkeypatch):
    monkeypatch.setenv("CTMR_FILTER_DEVICE", "0")
    assert not cascade_mod.device_enabled()
    monkeypatch.delenv("CTMR_FILTER_DEVICE")
    assert cascade_mod.device_enabled()


def test_canonical_keys_oversized_host_lane():
    """Serials past MAX_SERIAL_BYTES hash through the hashlib lane;
    distinct from every conforming key and from each other."""
    big_a, big_b = b"\x41" * 60, b"\x42" * 60
    small = b"\x41" * 8
    keys = canonical_keys(np.array([3, 3, 3]), np.array([500_000] * 3),
                          [big_a, big_b, small])
    assert len({k.tobytes() for k in keys}) == 3
    # Deterministic.
    again = canonical_keys(np.array([3]), np.array([500_000]), [big_a])
    assert np.array_equal(again[0], keys[0])


# -- zero false negatives across layouts and growth -----------------------


@pytest.mark.parametrize("layout,grow", [("bucket", True),
                                         ("open", False)])
def test_zero_false_negatives_across_layouts(monkeypatch, layout, grow):
    """Bucket runs with a tiny initial table + low threshold so growth
    fires mid-corpus and the capture spans a rehash (growth machinery
    is layout-shared, so the open variant skips the rehash and its
    extra per-capacity compiles — tier-1 budget)."""
    monkeypatch.setenv("CTMR_TABLE", layout)
    if grow:
        agg = TpuAggregator(capacity=1 << 8, batch_size=64, grow_at=0.5,
                            max_capacity=1 << 14)
    else:
        agg = TpuAggregator(capacity=1 << 10, batch_size=64)
    agg.enable_filter_capture()
    agg.ingest(corpus(n=150, dupes=25))
    if grow:
        assert agg.capacity > (1 << 8), "growth never fired"
    snap = agg.drain()
    total_cap = sum(len(v) for v in agg.filter_capture.values())
    assert total_cap == snap.total
    art = build_from_aggregator(agg, fp_rate=0.01)
    for iss, eh, sb in capture_identity_items(agg):
        assert art.query(iss, eh, sb), (iss, eh, sb.hex())


def test_capture_is_the_report_and_false_positives_stay_in_budget():
    """Two things the emitted artifact owes its readers beyond zero
    false negatives: the capture it is built from equals the drained
    report group for group (a replayed duplicate counts once in both),
    and serials never fed (21 bytes long, so no probe can be an
    included identity) read known at no more than twice the target
    rate, from a cascade and not a list of fingerprints."""
    fp_rate = 0.01
    agg = TpuAggregator(capacity=1 << 10, batch_size=64)
    agg.enable_filter_capture()
    agg.ingest(corpus(n=300, dupes=40))
    agg.ingest(corpus(n=200, dupes=20, issuer_cn="Filter CA B",
                      issuer=ISSUER_DER_B, base=5000))
    snap = agg.drain()
    captured = {
        (agg.registry.issuer_at(idx).id(), ExpDate.from_unix_hour(eh).id()):
            len(serials)
        for (idx, eh), serials in agg.filter_capture.items()}
    assert captured == dict(snap.counts)
    assert sorted(captured.values()) == [200, 300]

    art = build_from_aggregator(agg, fp_rate=fp_rate)
    rng = np.random.default_rng(20260805)
    probes = [rng.integers(0, 256, 21, dtype=np.uint8).tobytes()
              for _ in range(4000)]
    hits = sum(int(np.asarray(art.query_group(g, probes)).sum())
               for _key, g in sorted(art.groups.items()))
    assert hits / (len(probes) * len(art.groups)) <= 2 * fp_rate
    assert 0 < art.bits_per_entry() < 64
    assert art.max_layers() >= 1


def test_zero_false_negatives_sharded_layout():
    import jax
    from jax.sharding import Mesh

    from ct_mapreduce_tpu.agg.sharded_agg import ShardedAggregator

    mesh = Mesh(np.array(jax.devices()), ("shard",))
    agg = ShardedAggregator(mesh, capacity=1 << 13, batch_size=32)
    agg.enable_filter_capture()
    agg.ingest(corpus(n=120, dupes=20))
    snap = agg.drain()
    assert sum(len(v) for v in agg.filter_capture.values()) == snap.total
    art = build_from_aggregator(agg, fp_rate=0.01)
    for iss, eh, sb in capture_identity_items(agg):
        assert art.query(iss, eh, sb), (iss, eh, sb.hex())
    # Cross-group exactness is an fl01 (global-universe) guarantee:
    # every other group's keys sit in this group's excluded set, so a
    # known serial answers False for a neighbouring expiry bucket with
    # certainty (fl02 answers False only at 1 - fpRate).
    art01 = build_from_aggregator(agg, fp_rate=0.01, fmt="fl01")
    iss, eh, sb = capture_identity_items(agg)[0]
    assert not art01.query(iss, eh + 24, sb)


def test_oversized_serial_rides_capture_and_artifact():
    """Host-lane-only identities (oversized serials the device never
    sees) flow through capture → artifact → exact answers."""
    agg = TpuAggregator(capacity=1 << 10, batch_size=64)
    agg.enable_filter_capture()
    agg.ingest(corpus(n=40, dupes=0))
    big = b"\x9a" * 60
    idx, eh = next(iter(agg.filter_capture))
    # The host lane's insert path is _host_dedup; drive it directly
    # with a parsed-fields stand-in (minicert serials cap at 20 bytes,
    # so a real >46-byte cert cannot be minted here).
    class F:
        serial = big
        issuer_dn = "CN=Filter CA"
        crl_distribution_points = []

    agg.host_serials.setdefault((idx, eh), set())
    agg._host_dedup(F(), idx, eh)
    art = build_from_aggregator(agg, fp_rate=0.01)
    assert art.query(agg.registry.issuer_at(idx).id(), eh, big)


# -- determinism ----------------------------------------------------------


def test_artifact_deterministic_across_ingest_order():
    ents = corpus(n=90, dupes=0)
    rev = list(reversed(ents))
    blobs = []
    for order in (ents, rev):
        agg = TpuAggregator(capacity=1 << 10, batch_size=64)
        agg.enable_filter_capture()
        agg.ingest(order)
        blobs.append(build_from_aggregator(agg, fp_rate=0.01).to_bytes())
    assert blobs[0] == blobs[1]


def test_merged_fleet_filter_matches_serial_run(tmp_path):
    """The headline determinism gate: two 'workers' over disjoint
    halves, checkpointed and merged (agg/merge.py), must compile to
    the same bytes as one serial run over everything — worker-local
    issuer indices must cancel out of the canonical keys."""
    from ct_mapreduce_tpu.agg import merge
    from ct_mapreduce_tpu.filter import build_from_merged

    half_a = corpus(n=60, dupes=10, issuer_cn="Filter CA",
                    issuer=ISSUER_DER, base=1000)
    half_b = corpus(n=60, dupes=10, issuer_cn="Filter CA B",
                    issuer=ISSUER_DER_B, base=500_000)
    paths = []
    # Worker 0 sees B-then-A issuer ordering relative to the serial
    # run, so registry indices genuinely differ.
    for w, ents in enumerate((half_b, half_a)):
        agg = TpuAggregator(capacity=1 << 10, batch_size=64)
        agg.enable_filter_capture()
        agg.ingest(ents)
        p = str(tmp_path / f"agg.w{w}.npz")
        agg.save_checkpoint(p)
        paths.append(p)
    serial = TpuAggregator(capacity=1 << 10, batch_size=64)
    serial.enable_filter_capture()
    serial.ingest(half_a + half_b)
    sp = str(tmp_path / "agg.serial.npz")
    serial.save_checkpoint(sp)

    merged_blob = build_from_merged(
        merge.load_checkpoints(paths), fp_rate=0.01).to_bytes()
    serial_blob = build_from_merged(
        merge.load_checkpoints([sp]), fp_rate=0.01).to_bytes()
    assert merged_blob == serial_blob
    # And the in-memory serial build agrees with its checkpointed form.
    assert build_from_aggregator(serial, fp_rate=0.01).to_bytes() \
        == serial_blob


def test_merged_refuses_captureless_checkpoint(tmp_path):
    from ct_mapreduce_tpu.agg import merge
    from ct_mapreduce_tpu.filter import build_from_merged

    agg = TpuAggregator(capacity=1 << 10, batch_size=64)
    agg.ingest(corpus(n=20, dupes=0))  # capture OFF
    p = str(tmp_path / "nocap.npz")
    agg.save_checkpoint(p)
    merged = merge.load_checkpoints([p])
    assert merged.capture_missing == [p]
    with pytest.raises(ValueError, match="emitFilter"):
        build_from_merged(merged, fp_rate=0.01)
    art = build_from_merged(merged, fp_rate=0.01, allow_partial=True)
    assert art.n_serials == 0  # honest: nothing recoverable


# -- checkpoint interplay -------------------------------------------------


def test_checkpoint_unperturbed_when_filter_off(tmp_path):
    """emitFilter off: the .npz carries no filter keys and repeated
    saves of the same state are byte-identical (round-15 code must be
    invisible to pre-round-15 consumers)."""
    agg = TpuAggregator(capacity=1 << 10, batch_size=64)
    agg.ingest(corpus(n=30, dupes=5))
    p1, p2 = str(tmp_path / "a.npz"), str(tmp_path / "b.npz")
    agg.save_checkpoint(p1)
    agg.save_checkpoint(p2)
    b1, b2 = open(p1, "rb").read(), open(p2, "rb").read()
    assert b1 == b2
    z = np.load(p1, allow_pickle=True)
    assert "filter_keys" not in z.files and "filter_vals" not in z.files


def test_pre_round15_checkpoint_loads_cleanly(tmp_path):
    """A snapshot without filter keys (any pre-round-15 writer, or an
    emitFilter-off run) restores with capture off; enabling capture
    afterwards re-seeds from the restored host sets."""
    agg = TpuAggregator(capacity=1 << 10, batch_size=64)
    agg.ingest(corpus(n=25, dupes=0))
    p = str(tmp_path / "legacy.npz")
    agg.save_checkpoint(p)
    fresh = HostSnapshotAggregator(capacity=1 << 10)
    fresh.load_checkpoint(p)
    assert fresh.filter_capture is None
    assert fresh.drain().total == agg.drain().total


def test_capture_survives_checkpoint_roundtrip(tmp_path):
    agg = TpuAggregator(capacity=1 << 10, batch_size=64)
    agg.enable_filter_capture()
    agg.ingest(corpus(n=40, dupes=8))
    p = str(tmp_path / "cap.npz")
    agg.save_checkpoint(p)
    back = HostSnapshotAggregator(capacity=1 << 10)
    back.load_checkpoint(p)
    assert back.filter_capture == agg.filter_capture
    # want_serials re-arms so a resumed ingest keeps capturing.
    assert back.want_serials


def test_emission_writes_artifact_next_to_snapshot(tmp_path):
    agg = TpuAggregator(capacity=1 << 10, batch_size=64)
    agg.configure_filter_emission(str(tmp_path / "agg.filter"),
                                  fp_rate=0.02)
    agg.ingest(corpus(n=30, dupes=0))
    agg.save_checkpoint(str(tmp_path / "agg.npz"))
    art = read_artifact(str(tmp_path / "agg.filter"))
    assert art.fp_rate == 0.02
    assert art.n_serials == agg.drain().total


# -- config surface -------------------------------------------------------


def test_resolve_filter_layering(monkeypatch):
    monkeypatch.delenv("CTMR_EMIT_FILTER", raising=False)
    monkeypatch.delenv("CTMR_FILTER_PATH", raising=False)
    monkeypatch.delenv("CTMR_FILTER_FP_RATE", raising=False)
    r = resolve_filter(state_path="/x/agg.npz")
    assert (r.emit, r.path, r.fp_rate) == \
        (False, "/x/agg.npz.filter", 0.01)
    monkeypatch.setenv("CTMR_EMIT_FILTER", "1")
    monkeypatch.setenv("CTMR_FILTER_FP_RATE", "0.05")
    r = resolve_filter(state_path="/x/agg.npz")
    assert (r.emit, r.fp_rate) == (True, 0.05)
    # Explicit values beat env.
    r = resolve_filter(emit=False, path="/y/f.bin", fp_rate=0.2)
    assert (r.emit, r.path, r.fp_rate) == (False, "/y/f.bin", 0.2)
    # Unparseable env rate falls back to the default.
    monkeypatch.setenv("CTMR_FILTER_FP_RATE", "nope")
    assert resolve_filter().fp_rate == 0.01


def test_config_directives(tmp_path):
    from ct_mapreduce_tpu.config import CTConfig

    ini = tmp_path / "f.ini"
    ini.write_text("emitFilter = true\nfilterPath = /tmp/f.bin\n"
                   "filterFpRate = 0.001\n")
    cfg = CTConfig.load(["-config", str(ini)], env={})
    assert cfg.emit_filter and cfg.filter_path == "/tmp/f.bin"
    assert cfg.filter_fp_rate == 0.001
    assert "emitFilter" in cfg.usage() and "filterFpRate" in cfg.usage()


# -- serve integration ----------------------------------------------------


def test_filter_first_parity_under_concurrent_ingest():
    """The two-tier lookup answers exactly what the table-backed
    oracle answers while ingest keeps mutating the table: cascade
    false positives die at the table-confirm tier, cascade negatives
    are exact for the build-time corpus."""
    from ct_mapreduce_tpu.serve.server import MembershipOracle

    agg = TpuAggregator(capacity=1 << 10, batch_size=64)
    agg.enable_filter_capture()
    agg.ingest(corpus(n=120, dupes=20))
    items_known = [(idx, eh, sb)
                   for (idx, eh), serials in agg.filter_capture.items()
                   for sb in sorted(serials)[:40]]
    idx0, eh0 = next(iter(agg.filter_capture))
    items_unknown = [(idx0, eh0, bytes([200 + (j % 50), j % 251, 7]))
                     for j in range(60)]
    items_other = [(idx0 + 999, eh0, b"\x01\x02"),  # unseen issuer
                   (-1, eh0, b"\x01\x02"),
                   (idx0, eh0 + 999, b"\x01\x02")]
    items = items_known + items_unknown + items_other

    tiered = MembershipOracle(agg, filter_first=True, max_delay_s=0.001)
    plain = MembershipOracle(agg, filter_first=False, max_delay_s=0.001)
    assert tiered.filter_tier is not None
    stop = threading.Event()

    def churn():
        s = 0
        while not stop.is_set():
            agg.ingest(corpus(n=10, dupes=0, base=700_000 + s))
            s += 10

    t = threading.Thread(target=churn, daemon=True)
    t.start()
    try:
        for _ in range(6):
            a = [r[0] for r in tiered.query_raw(items)]
            b = [r[0] for r in plain.query_raw(items)]
            assert a == b
    finally:
        stop.set()
        t.join(timeout=5)
        tiered.close()
        plain.close()
    # The tier actually answered negatives without the table.
    from ct_mapreduce_tpu.telemetry.metrics import get_sink

    counters = get_sink().snapshot()["counters"]
    assert counters.get("serve.filter_negative", 0) > 0
    assert counters.get("serve.filter_forward", 0) > 0


def test_filter_routes_serve_artifact():
    import urllib.error
    import urllib.request

    from ct_mapreduce_tpu.serve.server import QueryServer

    agg = TpuAggregator(capacity=1 << 10, batch_size=64)
    agg.enable_filter_capture()
    agg.ingest(corpus(n=50, dupes=0))
    (idx, eh), serials = next(iter(agg.filter_capture.items()))
    iss = agg.registry.issuer_at(idx).id()
    exp_id = ExpDate.from_unix_hour(eh).id()
    sb = next(iter(serials))
    srv = QueryServer(agg, 0, filter_first=True).start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        full = FilterArtifact.from_bytes(
            urllib.request.urlopen(f"{base}/filter").read())
        assert full.query(iss, eh, sb)
        part = FilterArtifact.from_bytes(
            urllib.request.urlopen(f"{base}/filter/{iss}/{exp_id}").read())
        assert part.query(iss, eh, sb)
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(f"{base}/filter/unknown/2031-01-01")
        assert err.value.code == 404
        stats = srv.oracle.stats()
        assert stats["filter_first"] and stats["filter_serials"] > 0
    finally:
        srv.stop()


def test_filter_route_cold_tier_404():
    import urllib.error
    import urllib.request

    from ct_mapreduce_tpu.serve.server import QueryServer

    agg = TpuAggregator(capacity=1 << 10, batch_size=64)
    srv = QueryServer(agg, 0).start()  # filter_first off → cold tier
    try:
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/filter")
        assert err.value.code == 404
    finally:
        srv.stop()


# -- CLI ------------------------------------------------------------------


def test_ct_filter_cli_build_inspect_query(tmp_path):
    from ct_mapreduce_tpu.cmd import ct_filter

    agg = TpuAggregator(capacity=1 << 10, batch_size=64)
    agg.enable_filter_capture()
    agg.ingest(corpus(n=40, dupes=5))
    state = str(tmp_path / "agg.npz")
    agg.save_checkpoint(state)
    out_path = str(tmp_path / "run.filter")

    buf = io.StringIO()
    rc = ct_filter.main(["build", "-state", state, "-out", out_path],
                        out=buf)
    assert rc == 0
    built = json.loads(buf.getvalue())
    assert built["serials"] == agg.drain().total
    assert os.path.exists(out_path)

    buf = io.StringIO()
    assert ct_filter.main(
        ["inspect", "-artifact", out_path, "-json"], out=buf) == 0
    assert json.loads(buf.getvalue())["serials"] == built["serials"]

    (idx, eh), serials = next(iter(agg.filter_capture.items()))
    iss = agg.registry.issuer_at(idx).id()
    exp_id = ExpDate.from_unix_hour(eh).id()
    known = next(iter(serials)).hex()
    buf = io.StringIO()
    assert ct_filter.main(
        ["query", "-artifact", out_path, "-issuer", iss,
         "-expDate", exp_id, "-serial", known], out=buf) == 0
    assert ct_filter.main(
        ["query", "-artifact", out_path, "-issuer", iss,
         "-expDate", exp_id, "-serial", "deadbeefcafe" * 4],
        out=io.StringIO()) in (0, 1)  # FP possible, never an error
    assert ct_filter.main(
        ["query", "-artifact", out_path, "-issuer", "nobody",
         "-expDate", exp_id, "-serial", known],
        out=io.StringIO()) == 1
    # Captureless checkpoints are refused without -allowPartial.
    nocap = TpuAggregator(capacity=1 << 10, batch_size=64)
    nocap.ingest(corpus(n=10, dupes=0))
    ns = str(tmp_path / "nocap.npz")
    nocap.save_checkpoint(ns)
    assert ct_filter.main(
        ["build", "-state", ns, "-out", str(tmp_path / "x.filter")],
        out=io.StringIO()) == 2
