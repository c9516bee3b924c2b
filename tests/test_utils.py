"""Duration parsing, backoff, and telemetry sink tests."""

import io

import pytest

from ct_mapreduce_tpu.telemetry import metrics
from ct_mapreduce_tpu.telemetry.metrics import InMemSink, MetricsDumper
from ct_mapreduce_tpu.utils import JitteredBackoff, format_duration, parse_duration


def test_parse_duration_go_syntax():
    assert parse_duration("15m") == 900
    assert parse_duration("125ms") == 0.125
    assert parse_duration("5s") == 5
    assert parse_duration("2h45m") == 2 * 3600 + 45 * 60
    assert parse_duration("10m") == 600
    assert parse_duration("1.5s") == 1.5
    assert parse_duration("-30s") == -30
    assert parse_duration("0") == 0


def test_parse_duration_rejects_garbage():
    for bad in ("", "fifteen", "15", "m15", "15 m"):
        with pytest.raises(ValueError):
            parse_duration(bad)


def test_format_duration():
    assert format_duration(900) == "15m"
    assert format_duration(0.125) == "125ms"
    assert format_duration(2 * 3600 + 45 * 60) == "2h45m"
    assert format_duration(0) == "0s"
    assert parse_duration(format_duration(3725.5)) == 3725.5


def test_backoff_growth_and_cap():
    b = JitteredBackoff(min_s=0.5, max_s=300, jitter=False)
    ds = [b.duration() for _ in range(12)]
    assert ds[0] == 0.5
    assert ds[1] == 1.0
    assert all(x <= 300 for x in ds)
    assert ds[-1] == 300
    b.reset()
    assert b.duration() == 0.5


def test_backoff_jitter_bounds():
    b = JitteredBackoff(min_s=0.5, max_s=300, jitter=True)
    for _ in range(50):
        d = b.duration()
        assert 0.5 <= d <= 300


def test_metrics_sink_and_dumper():
    sink = InMemSink()
    metrics.set_sink(sink)
    metrics.incr_counter("certIsFilteredOut", "CA")
    metrics.incr_counter("certIsFilteredOut", "CA")
    metrics.incr_counter("insertCTWorker", "Inserted", value=5)
    metrics.set_gauge("entries_per_sec_per_chip", value=1e7)
    with metrics.measure("insertCTWorker", "Store"):
        pass
    snap = sink.snapshot()
    assert snap["counters"]["certIsFilteredOut.CA"] == 2
    assert snap["counters"]["insertCTWorker.Inserted"] == 5
    assert snap["gauges"]["entries_per_sec_per_chip"] == 1e7
    assert snap["samples"]["insertCTWorker.Store"]["count"] == 1

    out = io.StringIO()
    dumper = MetricsDumper(sink, period_s=3600, out=out)
    dumper.dump()
    text = out.getvalue()
    assert "certIsFilteredOut.CA: 2" in text
    assert "entries_per_sec_per_chip" in text
    metrics.set_sink(InMemSink())  # reset global for other tests


def test_build_device_batches_unique_valid_rows():
    """The shared on-device batch synthesis: every row is the signed
    template with a unique serial, lane counters span [0, G*B), epoch
    bytes 4..8 stay zero for the caller, and the oversize guard fires."""
    import numpy as np
    import pytest

    from ct_mapreduce_tpu.core import der as hostder
    from ct_mapreduce_tpu.utils import syncerts

    tpl = syncerts.make_template()
    g, b, pad = 2, 64, 1024
    datas, lens = syncerts.build_device_batches(tpl, g, b, pad)
    datas = np.asarray(datas)
    lens = np.asarray(lens)
    assert datas.shape == (g, b, pad)
    assert (lens == len(tpl.leaf_der)).all()

    seen = set()
    for gi in range(g):
        for li in (0, 1, b - 1):
            row = bytes(datas[gi, li, : lens[gi, li]])
            fields = hostder.parse_cert(row)  # still canonical DER
            assert fields.serial_len == syncerts.SERIAL_LEN
            serial = row[tpl.serial_off : tpl.serial_off + tpl.serial_len]
            assert serial[4:8] == b"\x00" * 4  # epoch bytes left zero
            cnt = int.from_bytes(serial[12:16], "big")
            assert cnt == gi * b + li  # lane counter layout
            assert serial not in seen
            seen.add(serial)

    with pytest.raises(ValueError):
        syncerts.build_device_batches(tpl, 1, 4, len(tpl.leaf_der) - 1)
