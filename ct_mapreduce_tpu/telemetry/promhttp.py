"""Prometheus ``/metrics`` + ``/healthz`` over a stdlib HTTP server.

The reference exposes one ``/health`` endpoint and pushes metrics to
StatsD (engine.go:50-86); a production deployment of THIS engine wants
pull-based scraping: ``metricsPort`` starts a background
``ThreadingHTTPServer`` rendering the primary
:class:`~ct_mapreduce_tpu.telemetry.metrics.InMemSink` snapshot in
Prometheus text exposition format (version 0.0.4) —

- counters → ``counter``
- gauges → ``gauge``
- timing samples → ``summary`` with p50/p95/p99 quantiles plus
  ``_sum``/``_count``

— and ``/healthz`` as JSON: engine stage, last-progress timestamp, and
the entry channel's depth, the three numbers that
distinguish "healthy", "decode-starved", and "wedged" at a glance.

No third-party client library: names are sanitized to the Prometheus
grammar (``[a-zA-Z_:][a-zA-Z0-9_:]*``) and rendering is plain string
assembly, asserted valid by the parser in tests/test_promhttp.py.
"""

from __future__ import annotations

import json
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Optional

from ct_mapreduce_tpu.telemetry import metrics as _metrics

_INVALID = re.compile(r"[^a-zA-Z0-9_:]")


def metric_name(key: str) -> str:
    """Dotted metric key → valid Prometheus metric name."""
    name = _INVALID.sub("_", key)
    if not name or name[0].isdigit():
        name = "_" + name
    return name


def _fmt(v: float) -> str:
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def render_prometheus(snap: dict) -> str:
    """Render an ``InMemSink.snapshot()`` dict as text exposition."""
    lines: list[str] = []
    for key, val in sorted(snap.get("counters", {}).items()):
        name = metric_name(key)
        lines.append(f"# TYPE {name} counter")
        lines.append(f"{name} {_fmt(val)}")
    for key, val in sorted(snap.get("gauges", {}).items()):
        name = metric_name(key)
        lines.append(f"# TYPE {name} gauge")
        lines.append(f"{name} {_fmt(val)}")
    for key, s in sorted(snap.get("samples", {}).items()):
        name = metric_name(key)
        lines.append(f"# TYPE {name} summary")
        for q, field in (("0.5", "p50"), ("0.95", "p95"), ("0.99", "p99")):
            if field in s:
                lines.append(f'{name}{{quantile="{q}"}} {_fmt(s[field])}')
        lines.append(f"{name}_sum {_fmt(s['sum'])}")
        lines.append(f"{name}_count {_fmt(s['count'])}")
    return "\n".join(lines) + "\n"


class MetricsServer:
    """Background ``/metrics`` + ``/healthz`` server (``metricsPort``).

    ``sink`` defaults to the global primary sink (always
    snapshot-capable — see ``metrics.set_sink``); ``health`` is an
    optional callable returning the ``/healthz`` JSON dict — a
    ``"healthy": False`` entry turns the response into a 503, anything
    else (including no provider) is 200. Port 0 binds an ephemeral
    port, resolved on :meth:`start` (tests use this).

    Round 23 fleet fan-in: ``fleet_metrics`` (callable returning a
    full text exposition — telemetry/fleetobs.render_fleet_metrics
    over the coordinator fabric's obs payloads) adds
    ``GET /metrics/fleet``; ``fleet_health`` (callable returning the
    fleetobs.fleet_health rollup dict) adds ``GET /healthz/fleet``
    with the same ``healthy: False`` → 503 contract. Both 404 when
    their provider is absent — a solo worker's surface is unchanged."""

    def __init__(self, port: int, host: str = "0.0.0.0", sink=None,
                 health: Optional[Callable[[], dict]] = None,
                 fleet_metrics: Optional[Callable[[], str]] = None,
                 fleet_health: Optional[Callable[[], dict]] = None):
        self.host = host
        self.port = int(port)
        self._sink = sink
        self._health = health
        self._fleet_metrics = fleet_metrics
        self._fleet_health = fleet_health
        self._server: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    def _snapshot(self) -> dict:
        sink = self._sink if self._sink is not None else _metrics.get_sink()
        snap = getattr(sink, "snapshot", None)
        return snap() if snap is not None else {}

    def healthz(self) -> tuple[int, dict]:
        body: dict = {"time": time.time()}
        if self._health is not None:
            try:
                body.update(self._health())
            except Exception as err:  # health probe must answer, not 500
                return 503, {"healthy": False,
                             "error": f"{type(err).__name__}: {err}"}
        code = 503 if body.get("healthy") is False else 200
        body.setdefault("healthy", code == 200)
        return code, body

    def fleet_healthz(self) -> tuple[int, dict]:
        """The ``/healthz/fleet`` rollup with the same 503 contract as
        the per-process probe — the body always renders (a load
        balancer acts on the code, an operator reads the JSON)."""
        try:
            body = dict(self._fleet_health())
        except Exception as err:  # the rollup must answer, not 500
            return 503, {"healthy": False,
                         "error": f"{type(err).__name__}: {err}"}
        code = 503 if body.get("healthy") is False else 200
        body.setdefault("healthy", code == 200)
        body.setdefault("time", time.time())
        return code, body

    def start(self) -> "MetricsServer":
        server = self

        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 (http.server API)
                path = self.path.split("?", 1)[0].rstrip("/") or "/"
                if path == "/metrics":
                    payload = render_prometheus(server._snapshot()).encode()
                    ctype = "text/plain; version=0.0.4; charset=utf-8"
                    code = 200
                elif path == "/healthz":
                    code, body = server.healthz()
                    payload = json.dumps(body).encode()
                    ctype = "application/json"
                elif (path == "/metrics/fleet"
                        and server._fleet_metrics is not None):
                    try:
                        payload = server._fleet_metrics().encode()
                        code = 200
                    except Exception as err:  # scrape must answer
                        payload = (f"# fleet fan-in failed: "
                                   f"{type(err).__name__}: {err}\n").encode()
                        code = 503
                    ctype = "text/plain; version=0.0.4; charset=utf-8"
                elif (path == "/healthz/fleet"
                        and server._fleet_health is not None):
                    code, body = server.fleet_healthz()
                    payload = json.dumps(body).encode()
                    ctype = "application/json"
                else:
                    self.send_error(404)
                    return
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(payload)))
                self.end_headers()
                self.wfile.write(payload)

            def log_message(self, *args):  # no per-scrape stderr spam
                pass

        self._server = ThreadingHTTPServer((self.host, self.port), Handler)
        self.port = self._server.server_address[1]  # resolve port 0
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="promhttp", daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None
