"""Flight recorder (telemetry/flight.py): a wedged/crashed run leaves
a post-mortem artifact — last trace spans + metric snapshots — on an
injected fold failure (the sink's own dump), on signals, and via the
excepthook."""

import base64
import datetime
import json
import os
import signal

import pytest

from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
from ct_mapreduce_tpu.ingest import leaf as leaflib
from ct_mapreduce_tpu.ingest.sync import AggregatorSink, RawBatch
from ct_mapreduce_tpu.telemetry import flight, metrics, trace
from ct_mapreduce_tpu.utils import minicert

UTC = datetime.timezone.utc
NOW = datetime.datetime(2025, 1, 1, tzinfo=UTC)

ISSUER = minicert.make_cert(serial=1, issuer_cn="Flight CA", is_ca=True)


def wire_batch(start: int, n: int) -> RawBatch:
    lis, eds = [], []
    for j in range(n):
        leaf = minicert.make_cert(
            serial=start + j, issuer_cn="Flight CA",
            subject_cn="flight.example", is_ca=False,
        )
        lis.append(base64.b64encode(
            leaflib.encode_leaf_input(leaf, 1000 + start + j)).decode())
        eds.append(base64.b64encode(
            leaflib.encode_extra_data([ISSUER])).decode())
    return RawBatch(lis, eds, start, "flight-log")


@pytest.fixture(autouse=True)
def _clean_recorder():
    prev_tracer = trace.get_tracer()
    yield
    flight.uninstall()
    trace._tracer = prev_tracer
    metrics.set_sink(metrics.InMemSink())


def test_drain_failure_leaves_flight_dump(tmp_path):
    """Injected exception in the fold mid-ingest: it reaches the caller
    of ``store_raw_batch`` as it is, AND the flight recorder writes a
    dump containing the last spans and a metric snapshot. A store
    thread catches and reports such a failure, so no excepthook would
    ever see it: the sink's dump is the only artifact."""
    trace.disable()
    trace.enable(ring_size=4096)
    metrics.set_sink(metrics.InMemSink())
    rec = flight.install(str(tmp_path), signals=False, excepthook=False)

    agg = TpuAggregator(capacity=1 << 12, batch_size=32, now=NOW)
    sink = AggregatorSink(agg, flush_size=32, device_queue_depth=2)
    boom = RuntimeError("drain stage exploded")
    calls = {"n": 0}
    orig_complete = sink._complete_item

    def failing_complete(pending, der_of):
        calls["n"] += 1
        if calls["n"] in (2, 3):
            raise boom
        orig_complete(pending, der_of)

    sink._complete_item = failing_complete
    with pytest.raises(RuntimeError) as exc_info:
        for i in range(4):
            sink.store_raw_batch(wire_batch(i * 32, 32))
    assert exc_info.value is boom

    assert rec.dumps, "no flight dump written on drain failure"
    doc = json.load(open(rec.dumps[0]))
    assert "drain stage exploded" in doc["reason"]
    assert doc["pid"] == os.getpid()
    # The last spans are in the artifact — including the ingest stages
    # that ran before the failure.
    names = {e["name"] for e in doc["trace_events"]}
    assert "ingest.decode" in names
    assert "ingest.submit" in names
    assert "device.readback" in names
    # ... and a metric snapshot taken at dump time.
    assert doc["current_metrics"] is not None
    counters = doc["current_metrics"]["counters"]
    assert counters.get("ct-fetch.foldedEntries") == 32
    # The next fold fails too, under close()'s barrier this time: it
    # raises again and writes NO second artifact for the same fault.
    with pytest.raises(RuntimeError):
        sink.close()
    assert len(rec.dumps) == 1
    assert len(list(tmp_path.iterdir())) == 1
    sink.close()  # the fault gone, the rest folds


def test_snapshot_ring_is_bounded_and_in_dump(tmp_path):
    metrics.set_sink(metrics.InMemSink())
    rec = flight.install(str(tmp_path), max_snapshots=4, signals=False,
                         excepthook=False)
    for i in range(10):
        metrics.incr_counter("tick", value=1)
        flight.record_snapshot()
    path = flight.dump("manual")
    doc = json.load(open(path))
    snaps = doc["metric_snapshots"]
    assert len(snaps) == 4  # last N only
    # Newest-window: the final retained snapshot saw all 10 ticks.
    assert snaps[-1]["metrics"]["counters"]["tick"] == 10
    assert snaps[0]["metrics"]["counters"]["tick"] == 7


def test_dump_noop_when_not_installed(tmp_path):
    assert not flight.installed()
    assert flight.dump("nobody listening") is None
    flight.record_snapshot()  # no-op, no raise
    assert list(tmp_path.iterdir()) == []


def test_sigusr1_dumps_without_dying(tmp_path):
    rec = flight.install(str(tmp_path), signals=True, excepthook=False)
    os.kill(os.getpid(), signal.SIGUSR1)
    # Signal delivery is synchronous for the main thread on the next
    # bytecode boundary; the dump happened and we are still alive.
    assert rec.dumps and os.path.exists(rec.dumps[-1])
    doc = json.load(open(rec.dumps[-1]))
    assert "signal" in doc["reason"]


def test_excepthook_chains_and_dumps(tmp_path):
    rec = flight.install(str(tmp_path), signals=False, excepthook=True)
    seen = {}
    prev = flight._prev_excepthook

    def spy(exc_type, exc, tb):
        seen["exc"] = exc

    flight._prev_excepthook = spy
    try:
        import sys

        err = ValueError("unhandled crash")
        sys.excepthook(ValueError, err, None)
        assert seen["exc"] is err  # chained to the previous hook
        assert rec.dumps
        assert "unhandled crash" in json.load(open(rec.dumps[0]))["reason"]
    finally:
        flight._prev_excepthook = prev
