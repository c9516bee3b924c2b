"""Prometheus /metrics + /healthz endpoint (telemetry/promhttp.py):
text-exposition validity (asserted by a parser), value parity with
InMemSink.snapshot(), and the health surface."""

import json
import re
import urllib.error
import urllib.request

import pytest

from ct_mapreduce_tpu.telemetry.metrics import InMemSink
from ct_mapreduce_tpu.telemetry.promhttp import (
    MetricsServer,
    metric_name,
    render_prometheus,
)

_NAME = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
_SAMPLE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>[^}]*)\})? (?P<value>\S+)$")


def parse_exposition(text: str) -> dict:
    """Minimal Prometheus text-format (0.0.4) parser: every sample line
    must parse, every sample's base name must have a TYPE declared
    first. Returns {name: {"type": ..., "samples": [(labels, value)]}}."""
    families: dict = {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            name, typ = rest.split()
            assert _NAME.match(name), name
            assert typ in ("counter", "gauge", "summary", "histogram",
                           "untyped")
            assert name not in families, f"duplicate TYPE for {name}"
            families[name] = {"type": typ, "samples": []}
            continue
        assert not line.startswith("#"), f"unexpected comment: {line}"
        m = _SAMPLE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        name = m.group("name")
        base = re.sub(r"_(sum|count)$", "", name)
        fam = families.get(name) or families.get(base)
        assert fam is not None, f"sample {name} without TYPE"
        fam["samples"].append((m.group("labels"), float(m.group("value"))))
    return families


def _populated_sink() -> InMemSink:
    sink = InMemSink()
    sink.incr_counter("ct-fetch.insertCertificate", 42)
    sink.incr_counter("aggregator.batches", 7)
    sink.set_gauge("ingest.decode_threads", 0.75)
    sink.set_gauge("aggregator.table_load", 0.12)
    for i in range(1, 101):
        sink.add_sample("ct-fetch.dispatchLockWait", i / 1000.0)
    return sink


def test_render_is_valid_exposition_and_matches_snapshot():
    sink = _populated_sink()
    snap = sink.snapshot()
    fams = parse_exposition(render_prometheus(snap))

    for key, val in snap["counters"].items():
        fam = fams[metric_name(key)]
        assert fam["type"] == "counter"
        assert fam["samples"] == [(None, val)]
    for key, val in snap["gauges"].items():
        fam = fams[metric_name(key)]
        assert fam["type"] == "gauge"
        assert fam["samples"] == [(None, val)]
    for key, s in snap["samples"].items():
        name = metric_name(key)
        fam = fams[name]
        assert fam["type"] == "summary"
        by_label = dict(fam["samples"])
        assert by_label['quantile="0.5"'] == s["p50"]
        assert by_label['quantile="0.95"'] == s["p95"]
        assert by_label['quantile="0.99"'] == s["p99"]


def test_metric_name_sanitization():
    assert metric_name("ct-fetch.storeCertificate") == \
        "ct_fetch_storeCertificate"
    assert metric_name("LogWorker.log/a.saveState") == \
        "LogWorker_log_a_saveState"
    assert _NAME.match(metric_name("0weird.key"))


def _get(url: str):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, resp.read().decode()


def test_server_metrics_and_healthz():
    sink = _populated_sink()
    depths = {"queue_lanes": 1, "queue_cap": 3}
    srv = MetricsServer(
        0, host="127.0.0.1", sink=sink,
        health=lambda: {"stage": "syncing",
                        "last_progress": "2026-08-04T00:00:00+00:00",
                        "serve": depths}).start()
    try:
        code, text = _get(f"http://127.0.0.1:{srv.port}/metrics")
        assert code == 200
        fams = parse_exposition(text)
        snap = sink.snapshot()
        # Counter/gauge values match the snapshot exactly.
        assert fams["ct_fetch_insertCertificate"]["samples"] == [(None, 42.0)]
        assert fams["ingest_decode_threads"]["samples"] == [(None, 0.75)]
        flat = dict(fams["ct_fetch_dispatchLockWait"]["samples"])
        assert flat['quantile="0.99"'] == \
            snap["samples"]["ct-fetch.dispatchLockWait"]["p99"]

        code, body = _get(f"http://127.0.0.1:{srv.port}/healthz")
        assert code == 200
        health = json.loads(body)
        assert health["healthy"] is True
        assert health["stage"] == "syncing"
        assert health["last_progress"].startswith("2026-08-04")
        assert health["serve"] == depths

        with pytest.raises(urllib.error.HTTPError) as err:
            _get(f"http://127.0.0.1:{srv.port}/nope")
        assert err.value.code == 404
    finally:
        srv.stop()


def test_server_healthz_unhealthy_and_failing_provider():
    srv = MetricsServer(0, host="127.0.0.1", sink=InMemSink(),
                        health=lambda: {"healthy": False,
                                        "stage": "wedged"}).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(f"http://127.0.0.1:{srv.port}/healthz")
        assert err.value.code == 503
        assert json.loads(err.value.read().decode())["stage"] == "wedged"
    finally:
        srv.stop()

    def boom():
        raise RuntimeError("probe exploded")

    srv2 = MetricsServer(0, host="127.0.0.1", sink=InMemSink(),
                         health=boom).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(f"http://127.0.0.1:{srv2.port}/healthz")
        assert err.value.code == 503
        assert "probe exploded" in err.value.read().decode()
    finally:
        srv2.stop()


def test_server_tracks_live_sink_updates():
    """/metrics renders the sink's CURRENT state per scrape (pull
    semantics), not a bind-time copy."""
    sink = InMemSink()
    srv = MetricsServer(0, host="127.0.0.1", sink=sink).start()
    try:
        sink.incr_counter("live.counter", 1)
        _, text = _get(f"http://127.0.0.1:{srv.port}/metrics")
        assert dict(parse_exposition(text)["live_counter"]["samples"]) \
            == {None: 1.0}
        sink.incr_counter("live.counter", 2)
        _, text = _get(f"http://127.0.0.1:{srv.port}/metrics")
        assert dict(parse_exposition(text)["live_counter"]["samples"]) \
            == {None: 3.0}
    finally:
        srv.stop()
