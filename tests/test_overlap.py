"""Overlapped ingest scheduler (ingest/overlap.py): parity, ordering,
and failure semantics.

Fixtures come from ``ct_mapreduce_tpu.utils.minicert`` (hand-assembled
canonical DER) so this suite runs on hosts without the ``cryptography``
package — the ingest path parses and never verifies, so synthetic
signature bytes are within contract.
"""

import base64
import datetime
import threading
import time

import numpy as np
import pytest

from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
from ct_mapreduce_tpu.ingest import leaf as leaflib
from ct_mapreduce_tpu.ingest.overlap import OverlapError
from ct_mapreduce_tpu.ingest.sync import AggregatorSink, RawBatch
from ct_mapreduce_tpu.native import leafpack
from ct_mapreduce_tpu.utils import minicert

UTC = datetime.timezone.utc
NOW = datetime.datetime(2025, 1, 1, tzinfo=UTC)

ISSUERS = [minicert.make_cert(serial=1, issuer_cn=f"Ovl CA {k}", is_ca=True)
           for k in range(2)]


def wire_batch(start: int, n: int, duplicate_of: int | None = None):
    """n wire entries alternating two issuers; serials start..start+n
    (or re-serials of an earlier window when ``duplicate_of`` is set,
    for cross-batch dedup coverage)."""
    lis, eds = [], []
    base = duplicate_of if duplicate_of is not None else start
    for j in range(n):
        k = j % 2
        leaf = minicert.make_cert(
            serial=base + j, issuer_cn=f"Ovl CA {k}",
            subject_cn="ovl.example", is_ca=False,
        )
        lis.append(base64.b64encode(
            leaflib.encode_leaf_input(leaf, 1000 + start + j)).decode())
        eds.append(base64.b64encode(
            leaflib.encode_extra_data([ISSUERS[k]])).decode())
    return RawBatch(lis, eds, start, "ovl-log")


def make_sink(overlap_workers: int, depth: int = 2, flush_size: int = 32):
    agg = TpuAggregator(capacity=1 << 12, batch_size=flush_size, now=NOW)
    sink = AggregatorSink(agg, flush_size=flush_size,
                          device_queue_depth=depth,
                          overlap_workers=overlap_workers)
    return agg, sink


def test_overlap_exact_parity_with_serial():
    """Same wire batches through the serial path and the overlap
    scheduler: (was_unknown totals, host_lane, table_count, per-issuer
    counts) must match EXACTLY — insertion order is preserved by the
    submit-stage reorder point, so even cross-batch duplicates
    attribute identically."""
    batches = [wire_batch(i * 64, 64) for i in range(4)]
    # Batch 4 duplicates batch 1's serials: dedup must attribute the
    # first sighting to batch 1 on both paths.
    batches.append(wire_batch(4 * 64, 64, duplicate_of=0))

    def run(overlap_workers):
        agg, sink = make_sink(overlap_workers)
        for rb in batches:
            sink.store_raw_batch(rb)
        sink.close()
        snap = agg.drain()
        return {
            "counts": snap.counts,
            "total": snap.total,
            "table_count": int(np.asarray(agg.table.count)),
            "host_lane": agg.metrics["host_lane"],
            "inserted": agg.metrics["inserted"],
            "known": agg.metrics["known"],
            "issuer_totals": agg.issuer_totals.copy(),
        }

    serial = run(0)
    over = run(2)
    assert serial["total"] == over["total"] == 4 * 64
    assert serial["table_count"] == over["table_count"]
    assert serial["host_lane"] == over["host_lane"] == 0
    assert serial["counts"] == over["counts"]
    assert serial["inserted"] == over["inserted"]
    assert serial["known"] == over["known"] == 64  # the duplicate batch
    np.testing.assert_array_equal(serial["issuer_totals"],
                                  over["issuer_totals"])


def test_overlap_ordered_drain_under_slow_consumer():
    """A slow drain consumer must not reorder completions (FIFO =
    submission order) nor stall submissions beyond the configured
    depth — batch N+1 submits while N still drains."""
    agg, sink = make_sink(overlap_workers=2, depth=2)
    events = []
    ev_lock = threading.Lock()
    orig_submit = sink._submit_chunk
    orig_complete = sink._complete_item

    def slow_complete(pending, der_of):
        time.sleep(0.05)
        with ev_lock:
            events.append(("complete", id(pending)))
        orig_complete(pending, der_of)

    def spy_submit(prep):
        items = orig_submit(prep)
        with ev_lock:
            for kind, payload, _ in items:
                if kind == "pending":
                    events.append(("submit", id(payload)))
        return items

    sink._complete_item = slow_complete
    sink._submit_chunk = spy_submit
    for i in range(5):
        sink.store_raw_batch(wire_batch(i * 32, 32))
    sink.close()
    assert agg.drain().total == 5 * 32

    sub_ids = [i for k, i in events if k == "submit"]
    com_ids = [i for k, i in events if k == "complete"]
    assert len(sub_ids) == len(com_ids) == 5
    # FIFO drain: completion order equals submission order.
    assert com_ids == sub_ids
    # Pipelining: at least one submit happened before the first
    # completion (the slow consumer did not serialize the stages).
    kinds = [k for k, _ in events]
    assert kinds.index("complete") >= 2


def test_overlap_decode_failure_surfaces_and_shuts_down():
    """A decode worker raising mid-epoch must neither hang the queues
    nor get swallowed: the failure latches, flush()/close() raise
    OverlapError with the original as __cause__, and work already
    submitted to the device is still completed (counts exact for it)."""
    agg, sink = make_sink(overlap_workers=2, depth=2)
    boom = RuntimeError("decoder exploded")
    orig_prepare = sink._prepare_chunk
    calls = {"n": 0}

    def failing_prepare(pairs):
        calls["n"] += 1
        if calls["n"] == 2:
            raise boom
        return orig_prepare(pairs)

    sink._prepare_chunk = failing_prepare
    deadline = time.monotonic() + 60.0  # the no-hang budget
    with pytest.raises(OverlapError) as exc_info:
        for i in range(4):
            sink.store_raw_batch(wire_batch(i * 32, 32))
        sink.flush()
    assert exc_info.value.__cause__ is boom
    # Subsequent submissions refuse immediately.
    with pytest.raises(OverlapError):
        sink.store_raw_batch(wire_batch(999 * 32, 32))
    with pytest.raises(OverlapError):
        sink.close()
    assert time.monotonic() < deadline, "shutdown hung"
    # Whatever reached the device before the failure folded exactly:
    # chunk 1 always did (ordered submit), chunk 2 died in decode.
    total = agg.drain().total
    assert total % 32 == 0 and 32 <= total <= 3 * 32


def test_overlap_flush_is_reusable_barrier():
    """flush() drains everything in flight but keeps the pipeline
    alive: a second wave of batches lands exactly on the same sink."""
    agg, sink = make_sink(overlap_workers=2)
    sink.store_raw_batch(wire_batch(0, 64))
    sink.flush()
    assert agg.drain().total == 64
    sink.store_raw_batch(wire_batch(64, 64))
    sink.close()
    assert agg.drain().total == 128


def test_issuer_too_long_status_skips_futile_redecode():
    """Satellite (ADVICE r05): a >=2 MiB issuer DER gets its own
    status (ISSUER_TOO_LONG) — the cert itself packed fine, so the
    batch must NOT pay a full-width redecode that cannot clear it —
    and the entry still lands via the exact host lane."""
    huge_issuer = minicert.make_cert(
        serial=1, issuer_cn="Huge CA", is_ca=True,
        extra_ext_bytes=(1 << 21) + 256,
    )
    assert len(huge_issuer) >= (1 << 21)
    small = [minicert.make_cert(serial=50 + i, issuer_cn="Ovl CA 0",
                                subject_cn="s.example", is_ca=False)
             for i in range(3)]
    victim = minicert.make_cert(serial=99, issuer_cn="Huge CA",
                                subject_cn="v.example", is_ca=False)

    lis = [base64.b64encode(leaflib.encode_leaf_input(d, i)).decode()
           for i, d in enumerate(small + [victim])]
    eds = ([base64.b64encode(
        leaflib.encode_extra_data([ISSUERS[0]])).decode()] * len(small)
        + [base64.b64encode(
            leaflib.encode_extra_data([huge_issuer])).decode()])

    # Decoder level: dedicated status on BOTH lanes of the fallback
    # matrix (native when a compiler exists, pure Python always).
    dec_py = leafpack._decode_python(lis, eds, 2048)
    assert dec_py.status[-1] == leafpack.ISSUER_TOO_LONG
    assert dec_py.length[-1] == len(victim)  # the cert row IS packed
    from ct_mapreduce_tpu.native import available
    if available():
        dec_nat = leafpack.decode_raw_batch(lis, eds, 2048)
        np.testing.assert_array_equal(dec_nat.status, dec_py.status)

    # Sink level: the narrow pre-decode stays a SINGLE decode (the old
    # overloaded TOO_LONG forced a futile full-width redecode here).
    pads_seen = []
    orig = leafpack.decode_raw_pages

    def spy(pages, pad_len, workers=None, threads=None):
        pads_seen.append(pad_len)
        return orig(pages, pad_len, workers=workers, threads=threads)

    agg, sink = make_sink(overlap_workers=0, flush_size=64)
    leafpack.decode_raw_pages = spy
    try:
        sink.store_raw_batch(RawBatch(lis, eds, 0, "log"))
        sink.flush()
    finally:
        leafpack.decode_raw_pages = orig
    assert pads_seen == [sink.PAD_LEN // 2], pads_seen
    # ... and the oversized-issuer entry still counted, exactly once.
    assert agg.drain().total == len(small) + 1


def test_overlap_queue_highwater_gauges():
    """The bounded-queue high-water marks (prepared window + drain
    queue) are tracked and exported as gauges — the smoke gate's
    handle for telling a decode-starved pipeline from a drain-starved
    one."""
    from ct_mapreduce_tpu.telemetry import metrics as tmetrics

    agg, sink = make_sink(overlap_workers=2, depth=2)
    ovl = sink._overlap
    for i in range(6):
        sink.store_raw_batch(wire_batch(i * 32, 32))
    ovl.drain_all()
    hw = ovl.publish_highwater()
    cap_prepared, cap_drain = ovl._max_prepared, ovl.queue_depth
    sink.close()
    assert 1 <= hw["prepared"] <= cap_prepared
    assert 0 <= hw["drain_queue"] <= cap_drain
    # Gauges really were exported through the metrics API.
    sink_metrics2 = tmetrics.InMemSink()
    prev = tmetrics.get_sink()
    tmetrics.set_sink(sink_metrics2)
    try:
        agg2, sink2 = make_sink(overlap_workers=2, depth=1)
        ovl2 = sink2._overlap
        for i in range(4):
            sink2.store_raw_batch(wire_batch(i * 32, 32))
        ovl2.drain_all()
        occ = ovl2.occupancy(1.0)
        sink2.close()
    finally:
        tmetrics.set_sink(prev)
    gauges = sink_metrics2.snapshot()["gauges"]
    for key in ("overlap.prepared_highwater", "overlap.prepared_capacity",
                "overlap.drain_queue_highwater",
                "overlap.drain_queue_capacity"):
        assert key in gauges, sorted(gauges)
    assert gauges["overlap.prepared_highwater"] >= 1
    assert "lock" in occ  # dispatch-lock wait is its own occupancy bucket


def test_overlap_lock_wait_sampled_outside_store_envelope():
    """dispatchLockWait is its own sample and the storeCertificate
    envelope opens only after the lock is held — a submit budget
    must not fold lock contention into submit cost."""
    from ct_mapreduce_tpu.telemetry import metrics as tmetrics

    sink_metrics = tmetrics.InMemSink()
    prev = tmetrics.get_sink()
    tmetrics.set_sink(sink_metrics)
    try:
        agg, sink = make_sink(overlap_workers=2, depth=2)
        for i in range(4):
            sink.store_raw_batch(wire_batch(i * 32, 32))
        sink.close()
    finally:
        tmetrics.set_sink(prev)
    samples = sink_metrics.snapshot()["samples"]
    assert "ct-fetch.dispatchLockWait" in samples
    assert "ct-fetch.storeCertificate" in samples
    # One lock sample per submitted chunk (4 chunks + flush barrier
    # paths), all non-negative.
    assert samples["ct-fetch.dispatchLockWait"]["count"] >= 4
    assert samples["ct-fetch.dispatchLockWait"]["min"] >= 0.0


def test_traced_overlap_run_has_its_three_stages(tmp_path):
    """A traced run of the real sink with overlap workers records
    ``ingest.decode``, ``ingest.submit`` and ``ingest.drain`` spans,
    one a chunk and each with busy time, and ``tools/traceview.py``
    reads the exported artifact into that per-stage summary
    (tests/test_trace.py shows the same on a fake sink)."""
    from ct_mapreduce_tpu.telemetry import trace
    from tools import traceview

    n_chunks = 4
    trace.enable(ring_size=4096)
    try:
        agg, sink = make_sink(overlap_workers=2, depth=2)
        for i in range(n_chunks):
            sink.store_raw_batch(wire_batch(i * 32, 32))
        sink.close()
        assert agg.drain().total == n_chunks * 32
        path = str(tmp_path / "overlap.json")
        trace.export(path)
    finally:
        trace.disable()
    stages = ("ingest.decode", "ingest.submit", "ingest.drain")
    summary = traceview.stage_summary(traceview.load(path), stages=stages)
    assert summary.pop("_wall_s") > 0
    assert set(summary) == set(stages)
    for name, s in summary.items():
        assert s["count"] == n_chunks, (name, s)
        assert s["busy_s"] > 0, (name, s)
