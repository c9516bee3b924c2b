"""Staged device queue (round 11): parity, growth, failure, and
observability semantics of the K-chunk resident envelope path
(`chunksPerDispatch` > 1 — ops/pipeline.staged_core, the sink's
staging ring, and PendingStaged's one-readback fold).

Fixtures are ``ct_mapreduce_tpu.utils.minicert`` wire entries (no
``cryptography`` dependency), mirroring tests/test_overlap.py — and
deliberately narrow: every sink here pins ``PAD_LEN`` down so chunks
decode into 512-byte rows (the minicert fixtures fit with room), which
roughly HALVES the walker's per-shape XLA compile cost on the CPU CI
box — and all tests share one (flush 32, capacity 1<<12, width 512)
shape so each program compiles once for the whole file.
"""

import base64
import datetime
import threading

import numpy as np
import pytest

from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
from ct_mapreduce_tpu.ingest import leaf as leaflib
from ct_mapreduce_tpu.ingest.overlap import OverlapError
from ct_mapreduce_tpu.ingest.sync import (
    AggregatorSink,
    RawBatch,
    resolve_staging,
)
from ct_mapreduce_tpu.storage.mockbackend import MockBackend
from ct_mapreduce_tpu.telemetry import metrics as tmetrics
from ct_mapreduce_tpu.utils import minicert

UTC = datetime.timezone.utc
NOW = datetime.datetime(2025, 1, 1, tzinfo=UTC)

FLUSH = 32  # lanes per chunk — matches test_overlap's walker shape
CAP = 1 << 12
K = 4  # chunks per dispatch: ONE staged-envelope compile for the file

ISSUERS = [minicert.make_cert(serial=1, issuer_cn=f"Stg CA {k}",
                              is_ca=True)
           for k in range(2)]


def wire_batch(start: int, n: int, duplicate_of: int | None = None,
               junk_lane: bool = False, oversized_serial: bool = False):
    """n wire entries alternating two issuers. ``junk_lane`` replaces
    one leaf with undecodable bytes (parse-error path);
    ``oversized_serial`` gives one cert a serial wider than the device
    schema (exact-host-lane spill path)."""
    lis, eds = [], []
    base = duplicate_of if duplicate_of is not None else start
    for j in range(n):
        k = j % 2
        if junk_lane and j == n // 2:
            lis.append(base64.b64encode(b"\x00\x01garbage-leaf").decode())
            eds.append(base64.b64encode(
                leaflib.encode_extra_data([ISSUERS[k]])).decode())
            continue
        serial = base + j
        serial_len = 16
        if oversized_serial and j == 1:
            # > MAX_SERIAL_BYTES (46): device-exactness gate routes the
            # lane to the exact host path on every ingest flavor.
            serial = (base + j) | (1 << 400)
            serial_len = None  # minicert sizes the body to the value
        leaf = minicert.make_cert(
            serial=serial, issuer_cn=f"Stg CA {k}",
            subject_cn="stg.example", is_ca=False, serial_len=serial_len,
        )
        lis.append(base64.b64encode(
            leaflib.encode_leaf_input(leaf, 1000 + start + j)).decode())
        eds.append(base64.b64encode(
            leaflib.encode_extra_data([ISSUERS[k]])).decode())
    return RawBatch(lis, eds, start, "stg-log")


def make_sink(overlap_workers: int, k_per: int, capacity: int = CAP,
              backend=None, staging_depth: int = 2, grow_at: float = 0.55,
              aggregator=None):
    agg = aggregator or TpuAggregator(capacity=capacity, batch_size=FLUSH,
                                      now=NOW, grow_at=grow_at)
    sink = AggregatorSink(agg, flush_size=FLUSH, backend=backend,
                          device_queue_depth=2 if overlap_workers else 0,
                          overlap_workers=overlap_workers,
                          chunks_per_dispatch=k_per,
                          staging_depth=staging_depth)
    # Narrow rows: minicert fixtures fit 512-byte rows, and the
    # compiled walker/envelope shapes stay file-wide shared (see
    # module docstring).
    sink.PAD_LEN = 1024
    return agg, sink


def replay(batches, overlap_workers: int, k_per: int, **kw):
    backend = MockBackend()
    agg, sink = make_sink(overlap_workers, k_per, backend=backend, **kw)
    for rb in batches:
        sink.store_raw_batch(rb)
    sink.close()
    snap = agg.drain()
    return {
        "counts": snap.counts,
        "total": snap.total,
        "table_count": agg._table_fill_exact(),
        "host_lane": agg.metrics["host_lane"],
        "inserted": agg.metrics["inserted"],
        "known": agg.metrics["known"],
        "overflow": agg.metrics["overflow"],
        "issuer_totals": agg.issuer_totals.copy(),
        "capacity": agg.capacity,
        # Per-(expDate, issuer) sets of stored serial ids — the
        # "serials parity" surface (first-seen PEM writes).
        "pems": {k: sorted(v) for k, v in backend.serials.items()},
        "agg": agg,
    }


def test_staged_exact_parity_with_serial():
    """Serial (per-chunk dispatch) vs staged (K-chunk envelope, both
    serial-dispatch and overlap-scheduler flavors) on a stream with
    cross-batch duplicates, an undecodable lane, and an
    oversized-serial host-lane spill: was-unknown attribution, host
    lane counts, probe-overflow spills, per-issuer totals, drained
    per-(issuer, expDate) counts, AND the per-entry serial sets the
    PEM backend stored must all match exactly."""
    batches = [
        wire_batch(0, FLUSH),
        wire_batch(FLUSH, FLUSH, junk_lane=True),
        wire_batch(2 * FLUSH, FLUSH, oversized_serial=True),
        wire_batch(3 * FLUSH, FLUSH),
        wire_batch(4 * FLUSH, FLUSH, duplicate_of=0),  # dedup window
        wire_batch(5 * FLUSH, FLUSH),
    ]
    # Fixture guard: the corpus must fit the narrow 512-byte rows the
    # whole file's compile-sharing rests on (see module docstring).
    from ct_mapreduce_tpu.native import leafpack

    dec = leafpack.decode_raw_batch(
        batches[2].leaf_inputs, batches[2].extra_datas, 512)
    assert not (dec.status == leafpack.TOO_LONG).any()

    serial = replay(batches, overlap_workers=0, k_per=1)
    staged = replay(batches, overlap_workers=0, k_per=K)
    staged_ovl = replay(batches, overlap_workers=2, k_per=K)
    assert serial["host_lane"] > 0  # the spill lane really spilled
    assert serial["known"] >= FLUSH  # the duplicate window really hit
    for name, got in (("staged", staged), ("staged+overlap", staged_ovl)):
        for field in ("counts", "total", "table_count", "host_lane",
                      "inserted", "known", "overflow", "pems"):
            assert got[field] == serial[field], (name, field)
        np.testing.assert_array_equal(got["issuer_totals"],
                                      serial["issuer_totals"])


def test_staged_open_layout_parity(monkeypatch):
    """Same parity contract on the open-addressed table layout (the
    envelope's table_insert dispatches by state type at trace time),
    with a ragged 7th chunk so the open-layout run also exercises the
    padded partial-envelope flush."""
    monkeypatch.setenv("CTMR_TABLE", "open")
    batches = [wire_batch(i * FLUSH, FLUSH) for i in range(6)]
    batches.append(wire_batch(6 * FLUSH, FLUSH, duplicate_of=0))
    serial = replay(batches, overlap_workers=0, k_per=1)
    staged = replay(batches, overlap_workers=2, k_per=K)
    assert serial["known"] >= FLUSH
    for field in ("counts", "total", "table_count", "host_lane",
                  "inserted", "known", "overflow", "pems"):
        assert staged[field] == serial[field], field
    np.testing.assert_array_equal(staged["issuer_totals"],
                                  serial["issuer_totals"])


def test_staged_partial_ring_flushes_at_barrier():
    """A ring holding fewer than K chunks must dispatch (as a padded
    partial envelope) at the flush barrier, and the
    ingest.dispatch_chunks sample must record the REAL chunk count —
    the early-flush visibility the metric exists for."""
    sink_m = tmetrics.InMemSink()
    prev = tmetrics.get_sink()
    tmetrics.set_sink(sink_m)
    try:
        batches = [wire_batch(i * FLUSH, FLUSH) for i in range(K - 1)]
        serial = replay(batches, overlap_workers=0, k_per=1)
        staged = replay(batches, overlap_workers=2, k_per=K)
    finally:
        tmetrics.set_sink(prev)
    assert staged["total"] == serial["total"] == (K - 1) * FLUSH
    assert staged["counts"] == serial["counts"]
    samples = sink_m.snapshot()["samples"]
    assert samples["ingest.dispatch_chunks"]["max"] == K - 1


def test_staged_run_fuses_k_chunks_an_execution():
    """What a staged run is made of, from its own spans and counters:
    2K chunks go to the device in 2 ``device.step_staged`` executions
    where the per-chunk path runs 2K ``device.step``s, every dispatch
    carries K chunks, and the staging copy has its span and its byte
    counter."""
    from ct_mapreduce_tpu.telemetry import trace

    batches = [wire_batch(i * FLUSH, FLUSH) for i in range(2 * K)]

    def traced(k_per):
        sink_m = tmetrics.InMemSink()
        prev = tmetrics.get_sink()
        tmetrics.set_sink(sink_m)
        tracer = trace.enable(ring_size=4096)
        t0 = tracer.now_us()
        try:
            out = replay(batches, overlap_workers=2, k_per=k_per)
            names = [e["name"] for e in tracer.events()
                     if e.get("ph") == "X" and e["ts"] >= t0]
        finally:
            trace.disable()
            tmetrics.set_sink(prev)
        return out, names, sink_m.snapshot()

    serial, serial_names, _ = traced(1)
    staged, names, snap = traced(K)
    assert staged["counts"] == serial["counts"]
    assert serial_names.count("device.step") == 2 * K
    assert names.count("device.step_staged") == 2
    assert names.count("device.step") == 0
    chunks = snap["samples"]["ingest.dispatch_chunks"]
    assert chunks["min"] == chunks["max"] == K
    assert names.count("ingest.h2d") > 0
    assert snap["counters"]["ingest.h2d_bytes"] > 0


def test_staged_ring_survives_error_latch():
    """A drain-stage failure latches the overlap pipeline mid-staging:
    close() raises OverlapError, chunks parked in the ring are dropped
    (never half-dispatched), and the aggregator — whose table buffer
    rode through donated envelope dispatches — remains fully usable
    for a follow-up serial ingest with exact counts."""
    agg, sink = make_sink(overlap_workers=2, k_per=K)
    boom = RuntimeError("drain exploded")
    orig = sink._complete_item
    calls = {"n": 0}

    def failing_complete(pending, der_of):
        calls["n"] += 1
        if calls["n"] == 1:
            raise boom
        return orig(pending, der_of)

    sink._complete_item = failing_complete
    with pytest.raises(OverlapError) as err:
        for i in range(3 * K):
            sink.store_raw_batch(wire_batch(i * FLUSH, FLUSH))
        sink.flush()
    assert err.value.__cause__ is boom
    with pytest.raises(OverlapError):
        sink.close()
    # The table state was not corrupted by the latch: whatever folded
    # before/after the failure is consistent, and fresh ingest over
    # the same aggregator (a new serial sink — same compiled walker
    # shape) keeps exact dedup behavior.
    before = agg.drain().total
    assert before % FLUSH == 0
    agg2, sink2 = make_sink(overlap_workers=0, k_per=1, aggregator=agg)
    sink2.store_raw_batch(wire_batch(900_000, 2 * FLUSH))
    sink2.flush()
    assert agg.drain().total == before + 2 * FLUSH


def test_staged_ring_depth_surfaces_in_healthz():
    """Satellite: the staging-ring occupancy rides queue_depths() (the
    /healthz surface) next to the prepared/drain gauges, and
    publish_highwater exports the ring gauges through the metrics
    API."""
    sink_m = tmetrics.InMemSink()
    prev = tmetrics.get_sink()
    tmetrics.set_sink(sink_m)
    try:
        agg, sink = make_sink(overlap_workers=2, k_per=K)
        for i in range(K + 1):
            sink.store_raw_batch(wire_batch(i * FLUSH, FLUSH))
        ovl = sink._overlap
        ovl.drain_all()
        depths = ovl.queue_depths()
        ovl.publish_highwater()
        sink.close()
    finally:
        tmetrics.set_sink(prev)
    for key in ("staging_ring", "staging_ring_capacity",
                "staging_ring_highwater"):
        assert key in depths, sorted(depths)
    assert depths["staging_ring_capacity"] == K
    assert 1 <= depths["staging_ring_highwater"] <= K
    assert depths["staging_ring"] == 0  # barrier flushed it
    gauges = sink_m.snapshot()["gauges"]
    assert gauges["overlap.staging_ring_capacity"] == K
    assert gauges["overlap.staging_ring_highwater"] >= 1
    # An unstaged sink must NOT grow the surface (no stale keys).
    agg2, sink2 = make_sink(overlap_workers=2, k_per=1)
    assert "staging_ring" not in sink2._overlap.queue_depths()
    sink2.close()


def test_staged_growth_mid_stream():
    """Mid-stream table growth under staging: the ring is (by
    construction) empty-or-dispatched when the envelope submit trips
    maybe_grow, outstanding envelopes fold, the table rebuilds, and
    the next envelopes re-enter the resident loop at the grown
    capacity — with every count matching the exact truth of the
    unique-serial stream (what the serial path produces by its own
    pinned tests). Capacities are chosen so the POST-grow envelope
    shape equals the parity tests' (already compiled; only the
    pre-grow shape pays a fresh compile)."""
    # Bucket layout rounds 1<<11 up to 3072 slots; at grow_at 0.55 the
    # 1,920 unique serials below trip a grow into 6144 slots — the
    # exact shape CAP=1<<12 rounds to in the tests above.
    start_cap = 1 << 11
    n_batches = 60
    total = n_batches * FLUSH
    batches = [wire_batch(i * FLUSH, FLUSH) for i in range(n_batches)]
    staged = replay(batches, overlap_workers=2, k_per=K,
                    capacity=start_cap)
    # Growth really happened mid-stream (the as-built slot count is
    # what the layout rounds start_cap to).
    start_slots = TpuAggregator(capacity=start_cap, batch_size=FLUSH,
                                now=NOW).capacity
    assert staged["capacity"] > start_slots
    # Exact truth of the stream: every serial unique, two issuers
    # alternating, one expDate per issuer, nothing spilled or lost
    # through the flush-ring → grow → re-enter sequence.
    assert staged["total"] == total
    assert staged["table_count"] == total
    assert staged["inserted"] == total and staged["known"] == 0
    assert staged["host_lane"] == 0 and staged["overflow"] == 0
    assert sorted(staged["counts"].values()) == [total // 2, total // 2]
    assert sum(len(v) for v in staged["pems"].values()) == total
    assert sorted(staged["issuer_totals"][staged["issuer_totals"] > 0]
                  .tolist()) == [total // 2, total // 2]


def test_staged_sharded_parity():
    """Staged lane over the mesh (ShardedAggregator delegates the
    envelope to per-chunk host-routed mesh steps — staged_h2d off, one
    deferred fold per staged flush): drained counts must match the
    single-chip serial path exactly."""
    import jax
    from jax.sharding import Mesh

    from ct_mapreduce_tpu.agg.sharded_agg import ShardedAggregator

    batches = [wire_batch(i * FLUSH, FLUSH) for i in range(6)]
    serial = replay(batches, overlap_workers=0, k_per=1)

    mesh = Mesh(np.array(jax.devices()[:4]), ("shard",))
    agg = ShardedAggregator(mesh, capacity=CAP, batch_size=FLUSH, now=NOW)
    assert agg.staged_h2d is False
    sink = AggregatorSink(agg, flush_size=FLUSH, overlap_workers=2,
                          chunks_per_dispatch=K)
    sink.PAD_LEN = 1024  # narrow rows, like make_sink
    for rb in batches:
        sink.store_raw_batch(rb)
    sink.close()
    snap = agg.drain()
    assert snap.total == serial["total"]
    assert snap.counts == serial["counts"]
    assert agg.metrics["host_lane"] == serial["host_lane"]


def test_resolve_staging_env_layering(monkeypatch):
    """Knob resolution: explicit kwarg > CTMR_* env > defaults; junk
    env values are ignored like the config layer does."""
    monkeypatch.delenv("CTMR_CHUNKS_PER_DISPATCH", raising=False)
    monkeypatch.delenv("CTMR_STAGING_DEPTH", raising=False)
    assert resolve_staging(0, 0) == (1, 2)  # defaults: off, double buf
    assert resolve_staging(8, 3) == (8, 3)  # explicit wins
    monkeypatch.setenv("CTMR_CHUNKS_PER_DISPATCH", "6")
    monkeypatch.setenv("CTMR_STAGING_DEPTH", "5")
    assert resolve_staging(0, 0) == (6, 5)  # env fills the gaps
    assert resolve_staging(2, 0) == (2, 5)  # kwarg beats env per-knob
    monkeypatch.setenv("CTMR_CHUNKS_PER_DISPATCH", "banana")
    monkeypatch.setenv("CTMR_STAGING_DEPTH", "")
    assert resolve_staging(0, 0) == (1, 2)  # junk env → defaults
