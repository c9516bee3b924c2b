"""The telemetry module itself: sink fanout topology, dumper output,
StatsD payload encoding (loopback UDP), sample ring bound, measure()
timing, and the snapshot percentiles (ISSUE 4 satellites)."""

import io
import socket
import time

from ct_mapreduce_tpu.telemetry import metrics
from ct_mapreduce_tpu.telemetry.metrics import (
    InMemSink,
    MetricsDumper,
    StatsdSink,
)


def _restore():
    metrics.set_sink(InMemSink())


def test_fanout_sinks_all_receive():
    primary, extra = InMemSink(), InMemSink()
    metrics.set_sink(primary, extra)
    try:
        metrics.incr_counter("fan", "c", value=2)
        metrics.set_gauge("fan", "g", value=7.0)
        metrics.add_sample("fan", "s", value=0.25)
        for s in (primary, extra):
            snap = s.snapshot()
            assert snap["counters"]["fan.c"] == 2
            assert snap["gauges"]["fan.g"] == 7.0
            assert snap["samples"]["fan.s"]["count"] == 1
        assert metrics.get_sink() is primary
        assert metrics.get_fanout() == [extra]
    finally:
        _restore()


def test_statsd_sink_demoted_to_fanout_keeps_snapshot():
    """set_sink(StatsdSink(...)) must NOT lose snapshot capability:
    an InMemSink stays primary and StatsD rides as fanout, so
    MetricsDumper, /metrics, and the flight recorder work in every
    configuration (the old code made StatsD the primary and
    ``snapshot()`` didn't exist on it)."""
    recv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    recv.bind(("127.0.0.1", 0))
    recv.settimeout(5.0)
    port = recv.getsockname()[1]
    sd = StatsdSink("127.0.0.1", port, prefix="ct-fetch.")
    metrics.set_sink(sd)
    try:
        # Primary is snapshot-capable; statsd still receives as fanout.
        primary = metrics.get_sink()
        assert hasattr(primary, "snapshot")
        assert metrics.get_fanout() == [sd]
        metrics.incr_counter("k")  # default value 1.0
        assert recv.recv(512) == b"ct-fetch.k:1.0|c"
        assert primary.snapshot()["counters"]["k"] == 1
    finally:
        _restore()
        recv.close()


def test_statsd_payload_encoding_loopback():
    recv = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    recv.bind(("127.0.0.1", 0))
    recv.settimeout(5.0)
    port = recv.getsockname()[1]
    sink = StatsdSink("127.0.0.1", port, prefix="p.")
    try:
        sink.incr_counter("a.count", 2.0)
        assert recv.recv(512) == b"p.a.count:2.0|c"
        sink.set_gauge("a.gauge", 1.5)
        assert recv.recv(512) == b"p.a.gauge:1.5|g"
        sink.add_sample("a.time", 0.25)  # seconds -> ms
        assert recv.recv(512) == b"p.a.time:250.000|ms"
    finally:
        sink.close()
        recv.close()


def test_statsd_socket_closed_on_replacement():
    """Replacing a StatsD sink via set_sink closes its UDP socket
    (ISSUE 4 satellite: no fd leak across reconfigurations); sends
    after close are silent no-ops."""
    sd = StatsdSink("127.0.0.1", 1)  # never actually sent to
    metrics.set_sink(sd)
    try:
        assert not sd._closed
        metrics.set_sink(InMemSink())
        assert sd._closed
        assert sd._sock.fileno() == -1
        sd.incr_counter("after.close", 1)  # must not raise
        sd.close()  # idempotent
    finally:
        _restore()


def test_sample_ring_bound():
    sink = InMemSink()
    n = sink.SAMPLE_RING
    for i in range(n + 500):
        sink.add_sample("ring", float(i))
    s = sink.snapshot()["samples"]["ring"]
    assert s["count"] == n
    # The ring keeps the NEWEST window.
    assert s["min"] == 500.0
    assert s["max"] == float(n + 499)


def test_measure_times_the_block():
    sink = InMemSink()
    metrics.set_sink(sink)
    try:
        with metrics.measure("timed", "block"):
            time.sleep(0.02)
        s = sink.snapshot()["samples"]["timed.block"]
        assert s["count"] == 1
        assert 0.015 <= s["mean"] < 5.0
    finally:
        _restore()


def test_snapshot_percentiles():
    """p50/p95/p99 join min/mean/max (the mean hides the tail that
    matters for dispatchLockWait / decodeBatch)."""
    sink = InMemSink()
    for i in range(1, 101):
        sink.add_sample("lat", float(i))
    s = sink.snapshot()["samples"]["lat"]
    assert s["p50"] == 50.0
    assert s["p95"] == 95.0
    assert s["p99"] == 99.0
    assert s["min"] == 1.0 and s["max"] == 100.0
    # One-sample series: every percentile is that sample.
    sink.add_sample("one", 0.5)
    one = sink.snapshot()["samples"]["one"]
    assert one["p50"] == one["p95"] == one["p99"] == 0.5


def test_dumper_output_format_includes_percentiles():
    sink = InMemSink()
    sink.incr_counter("certIsFilteredOut.CA", 2)
    sink.set_gauge("entries_per_sec_per_chip", 1e7)
    for i in range(1, 21):
        sink.add_sample("store", float(i) / 100.0)
    out = io.StringIO()
    MetricsDumper(sink, period_s=3600, out=out).dump()
    text = out.getvalue()
    assert "[C] certIsFilteredOut.CA: 2" in text
    assert "[G] entries_per_sec_per_chip" in text
    assert "p50=0.100000s" in text
    assert "p95=0.190000s" in text
    assert "p99=0.200000s" in text


def test_dumper_on_snapshot_feeds_recorder():
    """The on_snapshot hook receives every dumped snapshot (the flight
    recorder's feed), and a hook failure never kills the dump."""
    sink = InMemSink()
    sink.incr_counter("c", 1)
    seen = []
    out = io.StringIO()
    MetricsDumper(sink, 3600, out=out, on_snapshot=seen.append).dump()
    assert seen and seen[0]["counters"]["c"] == 1

    def boom(snap):
        raise RuntimeError("recorder died")

    out2 = io.StringIO()
    MetricsDumper(sink, 3600, out=out2, on_snapshot=boom).dump()
    assert "c: 1" in out2.getvalue()  # dump survived the hook
