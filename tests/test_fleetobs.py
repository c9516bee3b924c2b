"""Fleet observability plane (telemetry/fleetobs.py, round 23): obs
knob ladder, traceparent propagation, clock-skew correction math,
merged timelines, metrics fan-in parity, SLO threshold edges, and the
fleet health rollup — plus the promhttp fleet routes and the
batcher's trace-context adoption.

The live W=2 cross-process legs (one merged timeline across worker
pids, fleet-scrape parity against per-worker scrapes, SIGSTOP →
rollup 503) are tests/test_multiprocess.py::
test_fleet_observability_plane_live; everything here is in-process."""

import http.server
import json
import threading
import urllib.error
import urllib.request

import pytest

from ct_mapreduce_tpu.telemetry import fleetobs, metrics, trace
from ct_mapreduce_tpu.telemetry.fleetobs import ObsKnobs
from ct_mapreduce_tpu.telemetry.metrics import InMemSink
from ct_mapreduce_tpu.telemetry.promhttp import MetricsServer


# -- obs knob ladder -----------------------------------------------------


def test_resolve_obs_defaults(monkeypatch):
    for var in ("CTMR_FLEET_METRICS", "CTMR_SLO_MAX_INGEST_LAG",
                "CTMR_SLO_MAX_CKPT_AGE_S", "CTMR_SLO_MAX_FILTER_LAG",
                "CTMR_SLO_MAX_SERVE_P99_MS"):
        monkeypatch.delenv(var, raising=False)
    knobs = fleetobs.resolve_obs()
    assert knobs.fleet_metrics is True
    assert knobs.max_ingest_lag == 0
    assert knobs.max_ckpt_age_s == 0.0
    assert knobs.max_filter_lag == 0
    assert knobs.max_serve_p99_ms == 0.0
    assert not knobs.any_slo()


def test_resolve_obs_env_and_explicit(monkeypatch):
    monkeypatch.setenv("CTMR_FLEET_METRICS", "0")
    monkeypatch.setenv("CTMR_SLO_MAX_INGEST_LAG", "5")
    monkeypatch.setenv("CTMR_SLO_MAX_SERVE_P99_MS", "12.5")
    knobs = fleetobs.resolve_obs()
    assert knobs.fleet_metrics is False
    assert knobs.max_ingest_lag == 5
    assert knobs.max_serve_p99_ms == 12.5
    assert knobs.any_slo()
    # Explicit (config directive) outranks env; an unset explicit
    # (0 / None) falls through to the env layer.
    knobs = fleetobs.resolve_obs(fleet_metrics=True, max_ingest_lag=9,
                                 max_serve_p99_ms=0.0)
    assert knobs.fleet_metrics is True
    assert knobs.max_ingest_lag == 9
    assert knobs.max_serve_p99_ms == 12.5


# -- traceparent ---------------------------------------------------------


def test_traceparent_roundtrip():
    header, trace_id, span_id = trace.mint_traceparent()
    assert trace.parse_traceparent(header) == (trace_id, span_id)
    assert len(trace_id) == 32 and len(span_id) == 16
    assert trace.format_traceparent(trace_id, span_id) == header


@pytest.mark.parametrize("bad", [
    None, "", "garbage", "00-short-beef-01",
    "00-" + "g" * 32 + "-" + "1" * 16 + "-01",   # non-hex
    "00-" + "a" * 32 + "-" + "b" * 16,            # missing flags
    "0-" + "a" * 32 + "-" + "b" * 16 + "-01",     # bad version width
])
def test_traceparent_malformed(bad):
    assert trace.parse_traceparent(bad) is None


def test_trace_context_scoping_and_noop():
    assert trace.get_trace_context() is None
    with trace.trace_context("a" * 32, "b" * 16):
        assert trace.get_trace_context() == ("a" * 32, "b" * 16)
        # Falsy trace_id = no-op: the outer context survives.
        with trace.trace_context(None):
            assert trace.get_trace_context() == ("a" * 32, "b" * 16)
        with trace.trace_context("c" * 32, "d" * 16):
            assert trace.get_trace_context() == ("c" * 32, "d" * 16)
        assert trace.get_trace_context() == ("a" * 32, "b" * 16)
    assert trace.get_trace_context() is None


def test_span_args_carry_context_and_process_attrs():
    tracer = trace.SpanTracer(path=None, ring_size=64)
    trace.set_process_attrs(worker=3)
    try:
        with trace.trace_context("a" * 32, "b" * 16):
            with tracer.span("obs.test", cat="test", k=1):
                pass
        with tracer.span("obs.plain"):
            pass
    finally:
        trace.set_process_attrs(worker=None)
    evs = {e["name"]: e for e in tracer.events() if e.get("ph") == "X"}
    tagged = evs["obs.test"]["args"]
    assert tagged["trace_id"] == "a" * 32
    assert tagged["parent_id"] == "b" * 16
    assert tagged["worker"] == 3
    assert tagged["k"] == 1  # span-local args win, nothing dropped
    plain = evs["obs.plain"].get("args", {})
    assert "trace_id" not in plain and plain.get("worker") == 3


# -- clock skew + merge --------------------------------------------------


def test_clock_offset_and_correction():
    pair = {"wall": 1000.0, "mono": 100.0}
    assert fleetobs.clock_offset(pair) == 900.0
    # event at ts=5µs, tracer anchored at mono 10.0 → wall-epoch µs
    assert fleetobs.corrected_epoch_us(5.0, 10.0, 900.0) == 910e6 + 5.0


def _doc(worker, pid, wall_t0, mono_t0, events):
    return {
        "traceEvents": events,
        "otherData": {"wall_t0": wall_t0, "mono_t0": mono_t0,
                      "pid": pid, "process_attrs": {"worker": worker}},
    }


def test_merge_traces_rebases_and_corrects_skew():
    # Both workers started at mono=100; worker 1's wall clock reads
    # 0.5s fast. Its event really happened 100µs after worker 0's.
    d0 = _doc(0, 11, 1000.0, 100.0,
              [{"name": "a", "ph": "X", "ts": 0.0, "dur": 5.0,
                "pid": 11, "tid": 1}])
    d1 = _doc(1, 22, 1000.5, 100.0,
              [{"name": "b", "ph": "X", "ts": 100.0, "dur": 5.0,
                "pid": 22, "tid": 1}])

    # Without fabric pairs: each doc's own startup pair → worker 1's
    # wall skew leaks into the timeline (b lands 500100µs in).
    merged = fleetobs.merge_traces([d0, d1])
    by_name = {e["name"]: e for e in merged["traceEvents"]
               if e.get("ph") == "X"}
    assert by_name["a"]["ts"] == 0.0
    assert by_name["b"]["ts"] == pytest.approx(500100.0)
    assert merged["otherData"]["merged_from"] == 2
    assert merged["otherData"]["skew_corrected"] is False

    # Fabric pair for worker 1 carries its TRUE offset → corrected.
    pairs = {1: {"wall": 1000.0, "mono": 100.0}}
    merged = fleetobs.merge_traces([d0, d1], pairs=pairs)
    by_name = {e["name"]: e for e in merged["traceEvents"]
               if e.get("ph") == "X"}
    assert by_name["a"]["ts"] == 0.0
    assert by_name["b"]["ts"] == pytest.approx(100.0)
    assert merged["otherData"]["skew_corrected"] is True
    labels = {e["args"]["name"] for e in merged["traceEvents"]
              if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert labels == {"worker 0 (pid 11)", "worker 1 (pid 22)"}


# -- obs payloads --------------------------------------------------------


def _payload(worker, counters=None, gauges=None, samples=None,
             fleet=None, slo=None, wall=None):
    import time as _time

    sink = InMemSink()
    for k, v in (counters or {}).items():
        sink.incr_counter(k, v)
    for k, v in (gauges or {}).items():
        sink.set_gauge(k, v)
    for k, vals in (samples or {}).items():
        for v in vals:
            sink.add_sample(k, v)
    raw = fleetobs.build_obs_payload(worker, 2, fleet_stats=fleet,
                                     slo=slo, sink=sink)
    doc = fleetobs.parse_obs_payload(raw)
    assert doc is not None
    if wall is not None:
        doc["wall"] = wall
    return doc


def test_obs_payload_roundtrip_and_tolerant_parse():
    doc = _payload(1, counters={"a.b": 3},
                   fleet={"role": "leader"}, slo={"degraded": []})
    assert doc["worker"] == 1 and doc["num_workers"] == 2
    assert doc["metrics"]["counters"]["a.b"] == 3.0
    assert doc["fleet"]["role"] == "leader"
    assert "wall" in doc and "mono" in doc

    assert fleetobs.parse_obs_payload("not json {") is None
    assert fleetobs.parse_obs_payload(json.dumps([1, 2])) is None
    assert fleetobs.parse_obs_payload(
        json.dumps({"v": fleetobs.OBS_VERSION + 1, "metrics": {}})) is None

    raw = {0: json.dumps({"v": 1, "worker": 0, "metrics": {}}),
           1: "garbage"}
    got = fleetobs.collect_fleet_obs(raw)
    assert list(got) == [0]


def test_clock_pairs_from_obs():
    docs = {0: {"wall": 10.0, "mono": 2.0}, 1: {"wall": 11.0}}
    pairs = fleetobs.clock_pairs_from_obs(docs)
    assert pairs == {0: {"wall": 10.0, "mono": 2.0}}


# -- metrics fan-in ------------------------------------------------------


def test_render_fleet_metrics_parity_and_labels():
    payloads = {
        0: _payload(0, counters={"serve.requests": 3, "only.w0": 1},
                    gauges={"fleet.is_leader": 1.0},
                    samples={"serve.wait_s": [0.01, 0.02]}),
        1: _payload(1, counters={"serve.requests": 4.5}),
    }
    body = fleetobs.render_fleet_metrics(payloads)
    lines = body.splitlines()
    assert 'serve_requests{worker="0"} 3' in lines
    assert 'serve_requests{worker="1"} 4.5' in lines
    assert "serve_requests 7.5" in lines        # fleet-summed
    assert "only_w0 1" in lines                 # single-worker total
    # Gauges/samples render per-worker only — no unlabeled sum line.
    assert 'fleet_is_leader{worker="0"} 1' in lines
    assert not any(line.startswith("fleet_is_leader ") for line in lines)
    assert 'serve_wait_s_count{worker="0"} 2' in lines

    assert fleetobs.fleet_counter_parity(body) == []
    # A tampered total is caught (the smoke gate's assertion).
    broken = body.replace("\nserve_requests 7.5\n",
                          "\nserve_requests 9\n")
    assert fleetobs.fleet_counter_parity(broken) == ["serve_requests"]


# -- SLO rules -----------------------------------------------------------


class _FakeTracer:
    def __init__(self, durs_us):
        self._durs = durs_us

    def events(self):
        return [{"name": "serve.wait", "ph": "X", "ts": 0.0, "dur": d}
                for d in self._durs] + [{"name": "other", "ph": "X",
                                         "ts": 0.0, "dur": 1e9}]


def test_serve_p99_ms():
    durs = [1000.0 * (i + 1) for i in range(100)]  # 1ms..100ms
    assert fleetobs.serve_p99_ms(_FakeTracer(durs)) == \
        pytest.approx(99.0)
    assert fleetobs.serve_p99_ms(_FakeTracer([])) is None


def test_evaluate_slos_threshold_edges():
    knobs = ObsKnobs(fleet_metrics=True, max_ingest_lag=10,
                     max_ckpt_age_s=5.0, max_filter_lag=2,
                     max_serve_p99_ms=50.0)
    snap = {"gauges": {"ingest.lag_entries.log-a": 11.0,
                       "ingest.lag_entries.log-b": 3.0,
                       "unrelated.gauge": 99.0}}
    values, degraded = fleetobs.evaluate_slos(
        knobs, snap, now=100.0, last_checkpoint_wall=90.0,
        filter_epoch_lag=3, p99_ms=60.0)
    assert values["ingest_lag_entries"] == 11.0  # worst log wins
    assert values["checkpoint_age_s"] == 10.0
    assert values["filter_epoch_lag"] == 3.0
    assert values["serve_p99_ms"] == 60.0
    assert len(degraded) == 4

    # At-threshold values do NOT breach (strictly greater-than).
    snap = {"gauges": {"ingest.lag_entries.log-a": 10.0}}
    values, degraded = fleetobs.evaluate_slos(
        knobs, snap, now=100.0, last_checkpoint_wall=95.0,
        filter_epoch_lag=2, p99_ms=50.0)
    assert degraded == []

    # Checkpoint age grades against max(threshold, cadence): a 30s
    # cadence can't flap a 5s threshold.
    _, degraded = fleetobs.evaluate_slos(
        knobs, None, now=100.0, last_checkpoint_wall=90.0,
        checkpoint_period_s=30.0)
    assert degraded == []
    # ... but beyond the cadence it still breaches.
    _, degraded = fleetobs.evaluate_slos(
        knobs, None, now=131.0, last_checkpoint_wall=100.0,
        checkpoint_period_s=30.0)
    assert degraded and "checkpoint_age" in degraded[0]

    # No first checkpoint yet → no signal, no flapping at startup.
    values, degraded = fleetobs.evaluate_slos(
        knobs, None, now=100.0, last_checkpoint_wall=0.0)
    assert "checkpoint_age_s" not in values and degraded == []

    # Disabled thresholds record values but never degrade.
    off = ObsKnobs(fleet_metrics=True, max_ingest_lag=0,
                   max_ckpt_age_s=0.0, max_filter_lag=0,
                   max_serve_p99_ms=0.0)
    snap = {"gauges": {"ingest.lag_entries.log-a": 1e9}}
    values, degraded = fleetobs.evaluate_slos(
        off, snap, now=1e9, last_checkpoint_wall=1.0,
        filter_epoch_lag=1000, p99_ms=1e6)
    assert values and degraded == []


def test_publish_slo_gauges():
    fleetobs.publish_slo_gauges({"ingest_lag_entries": 11.0}, ["breach"])
    gauges = metrics.get_sink().snapshot()["gauges"]
    assert gauges["slo.ingest_lag_entries"] == 11.0
    assert gauges["slo.degraded"] == 1.0
    fleetobs.publish_slo_gauges({}, [])
    assert metrics.get_sink().snapshot()["gauges"]["slo.degraded"] == 0.0


# -- fleet health rollup -------------------------------------------------


def _health_payloads(now):
    p0 = _payload(0, gauges={"ckpt.chain_length": 3.0},
                  fleet={"role": "leader", "checkpoint_epoch": 5,
                         "claims": ["log-a"], "checkpoints_run": 2},
                  slo={"degraded": []}, wall=now)
    p1 = _payload(1, fleet={"role": "follower", "checkpoint_epoch": 5},
                  wall=now - 1.0)
    return p0, p1


def test_fleet_health_rollup():
    now = 1_000_000.0
    p0, p1 = _health_payloads(now)
    body = fleetobs.fleet_health({0: p0, 1: p1}, 2, 10.0, now=now)
    assert body["healthy"] is True
    assert body["workers_reporting"] == 2 and body["missing"] == []
    assert body["workers"]["0"]["role"] == "leader"
    assert body["leader_epoch_skew"] == 0
    assert body["ckpt_chain_depth"] == {"0": 3.0}

    # Missing worker → degraded.
    body = fleetobs.fleet_health({0: p0}, 2, 10.0, now=now)
    assert body["healthy"] is False
    assert any("worker 1 not reporting" in r for r in body["degraded"])

    # Stale heartbeat (TTL'd payload lingering) → degraded.
    p0s, p1s = _health_payloads(now)
    p1s["wall"] = now - 20.0
    body = fleetobs.fleet_health({0: p0s, 1: p1s}, 2, 10.0, now=now)
    assert body["healthy"] is False
    assert any("stale" in r for r in body["degraded"])

    # Epoch skew of 1 is normal propagation; 2+ degrades.
    p0a, p1a = _health_payloads(now)
    p1a["fleet"]["checkpoint_epoch"] = 4
    assert fleetobs.fleet_health(
        {0: p0a, 1: p1a}, 2, 10.0, now=now)["healthy"] is True
    p1a["fleet"]["checkpoint_epoch"] = 3
    body = fleetobs.fleet_health({0: p0a, 1: p1a}, 2, 10.0, now=now)
    assert body["healthy"] is False
    # ... and the reason names who lags (a SIGSTOP'd follower trips
    # this rule before its payload goes stale).
    assert "leader-epoch skew 2 (worker 1 behind)" in body["degraded"]

    # No leader reporting → degraded.
    p0b, p1b = _health_payloads(now)
    p0b["fleet"]["role"] = "follower"
    body = fleetobs.fleet_health({0: p0b, 1: p1b}, 2, 10.0, now=now)
    assert body["healthy"] is False
    assert any("no leader" in r for r in body["degraded"])

    # A worker's SLO breach surfaces in the rollup.
    p0c, p1c = _health_payloads(now)
    p1c["slo"] = {"degraded": ["ingest_lag 11 > 10"]}
    body = fleetobs.fleet_health({0: p0c, 1: p1c}, 2, 10.0, now=now)
    assert body["healthy"] is False
    assert any("worker 1 slo" in r for r in body["degraded"])


# -- promhttp fleet routes -----------------------------------------------


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, resp.read().decode()


def test_metrics_server_fleet_routes():
    health = {"healthy": True, "workers_reporting": 2}
    srv = MetricsServer(
        0, host="127.0.0.1", sink=InMemSink(),
        fleet_metrics=lambda: 'x{worker="0"} 1\n',
        fleet_health=lambda: dict(health)).start()
    try:
        code, text = _get(f"http://127.0.0.1:{srv.port}/metrics/fleet")
        assert code == 200 and 'x{worker="0"} 1' in text
        code, text = _get(f"http://127.0.0.1:{srv.port}/healthz/fleet")
        assert code == 200
        assert json.loads(text)["workers_reporting"] == 2

        health["healthy"] = False
        health["degraded"] = ["worker 1 not reporting"]
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(f"http://127.0.0.1:{srv.port}/healthz/fleet")
        assert err.value.code == 503
        assert json.loads(err.value.read().decode())["degraded"] == \
            ["worker 1 not reporting"]
    finally:
        srv.stop()


def test_metrics_server_fleet_routes_absent_and_failing():
    srv = MetricsServer(0, host="127.0.0.1", sink=InMemSink()).start()
    try:
        for route in ("/metrics/fleet", "/healthz/fleet"):
            with pytest.raises(urllib.error.HTTPError) as err:
                _get(f"http://127.0.0.1:{srv.port}{route}")
            assert err.value.code == 404
    finally:
        srv.stop()

    def boom():
        raise RuntimeError("fabric down")

    srv2 = MetricsServer(0, host="127.0.0.1", sink=InMemSink(),
                         fleet_metrics=boom, fleet_health=boom).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(f"http://127.0.0.1:{srv2.port}/metrics/fleet")
        assert err.value.code == 503
        assert "fabric down" in err.value.read().decode()
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(f"http://127.0.0.1:{srv2.port}/healthz/fleet")
        assert err.value.code == 503
    finally:
        srv2.stop()


# -- batcher trace-context adoption --------------------------------------


def test_batcher_adopts_single_submitter_context():
    from ct_mapreduce_tpu.serve.batcher import MicroBatcher

    captured = []

    def run_batch(items):
        captured.append(trace.get_trace_context())
        return items

    mb = MicroBatcher(run_batch, max_batch=64, max_delay_s=0.001)
    try:
        with trace.trace_context("a" * 32, "b" * 16):
            mb.submit([1, 2])
        mb.submit([3])
    finally:
        mb.close()
    # Single-context batch adopts the submitter's ids on the worker
    # thread; a context-free batch stays untagged.
    assert captured[0] == ("a" * 32, "b" * 16)
    assert captured[1] is None


# -- query client propagation + query-plane SLO 503 ----------------------


def test_query_client_mints_and_sends_traceparent():
    from ct_mapreduce_tpu.serve.client import QueryClient

    seen = []

    class Recorder(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            seen.append(self.headers.get(trace.TRACEPARENT_HEADER))
            body = json.dumps({"healthy": True}).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    httpd = http.server.HTTPServer(("127.0.0.1", 0), Recorder)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    tracer = trace.enable(path=None)
    n_before = len(tracer.events())
    try:
        QueryClient(f"127.0.0.1:{httpd.server_port}").healthz()
    finally:
        trace.disable()
        httpd.shutdown()
        thread.join(timeout=5)
    assert len(seen) == 1
    ids = trace.parse_traceparent(seen[0])
    assert ids is not None
    spans = [e for e in tracer.events()[n_before:]
             if e.get("name") == "query.client"]
    assert spans, "client did not record a query.client span"
    # The span carries the SAME trace id the wire header carried — the
    # merge-time correlation key.
    assert spans[-1]["args"]["trace_id"] == ids[0]


def test_query_server_healthz_503_on_slo_breach():
    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
    from ct_mapreduce_tpu.serve.server import QueryServer

    agg = TpuAggregator(capacity=1 << 10, batch_size=64)
    srv = QueryServer(agg, 0, host="127.0.0.1").start()
    try:
        code, text = _get(f"http://127.0.0.1:{srv.port}/healthz")
        assert code == 200 and json.loads(text)["healthy"] is True

        srv.slo_check = lambda: ["ingest_lag 11 > 10"]
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(f"http://127.0.0.1:{srv.port}/healthz")
        assert err.value.code == 503
        body = json.loads(err.value.read().decode())
        assert body["healthy"] is False
        assert body["degraded"] == ["ingest_lag 11 > 10"]

        # A crashing probe degrades (the probe must answer, not 500).
        def boom():
            raise RuntimeError("rule layer exploded")

        srv.slo_check = boom
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(f"http://127.0.0.1:{srv.port}/healthz")
        assert err.value.code == 503
        assert "rule layer exploded" in err.value.read().decode()
    finally:
        srv.stop()
