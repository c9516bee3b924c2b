#!/usr/bin/env python3
"""A loopback CT log front end in a process of its own (no JAX, none of
the program's code), on the cores its spec names: ``get-sth`` and
``get-entries`` for the logs of one run, HTTP/1.1.

Every page of the run is built from the seed before the server says it
is ready (while the parent loads JAX), so that serving one is a lookup
and a write: the generator stays out of the loop it measures. A request
that is not a whole page at a page boundary is built when it comes.

Each log's tree stays at its warm-up prefix until ``/control/open``;
then it is whole. Every ``get-entries`` response is stamped with
``time.monotonic()`` (system-wide on Linux, so the parent can set the
stamps beside its own) when its first byte is about to be written.

  /<log>/ct/v1/get-sth, /<log>/ct/v1/get-entries?start=&end=
  /control/open       open every log; answers {"opened_at": t}
  /control/stamps     every page served so far, for the parent
                      (?since=n: from the n-th on, for one that polls)

Started as ``python logserver.py <spec.json>``; prints one JSON line
with its port once it listens.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import fixture as fx  # noqa: E402


class LogState:
    def __init__(self, spec: fx.LogSpec, seed: int):
        self.run = fx.RunFixture(spec, seed)
        self.templates = fx.Templates()
        # (log, start) -> the body of the whole page that starts there.
        self.bodies = {
            (log.index, start): log.page_body(
                self.templates, start, start + spec.page - 1)
            for log in self.run.logs
            for start in range(0, log.total, spec.page)}
        self.opened_at: float | None = None
        self.lock = threading.Lock()
        # (log, start, count, t_request, t_response, t_done)
        self.pages: list[tuple] = []

    def tree_size(self, log: fx.LogFixture) -> int:
        return log.total if self.opened_at is not None else log.warm


def make_handler(state: LogState):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def _send(self, status: int, body: bytes) -> None:
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):  # noqa: N802
            t_req = time.monotonic()
            parsed = urlparse(self.path)
            path = parsed.path
            if path.startswith("/control/"):
                return self._control(path, parse_qs(parsed.query))
            try:
                k = int(path.split("/")[1].removeprefix("log"))
                log = state.run.logs[k]
            except (ValueError, IndexError):
                return self._send(404, b"no such log")
            if path.endswith("/ct/v1/get-sth"):
                return self._send(200, json.dumps({
                    "tree_size": state.tree_size(log),
                    "timestamp": fx.TS_BASE_MS}).encode())
            if not path.endswith("/ct/v1/get-entries"):
                return self._send(404, b"not found")
            q = parse_qs(parsed.query)
            start, end = int(q["start"][0]), int(q["end"][0])
            if not (0 <= start <= end and start < state.tree_size(log)):
                return self._send(400, b"range beyond tree size")
            end = min(end, state.tree_size(log) - 1)
            body = state.bodies.get((k, start))
            if body is None or end - start + 1 < log.spec.page:
                body = log.page_body(state.templates, start, end)
            count = min(end, start + log.spec.page - 1) - start + 1
            t_resp = time.monotonic()
            self._send(200, body)
            with state.lock:
                state.pages.append((k, start, count, t_req, t_resp,
                                    time.monotonic()))

        def _control(self, path: str, query: dict) -> None:
            if path == "/control/open":
                with state.lock:
                    if state.opened_at is None:
                        state.opened_at = time.monotonic()
                body = {"opened_at": state.opened_at}
            elif path == "/control/stamps":
                with state.lock:
                    body = {"opened_at": state.opened_at, "pages": state.pages[
                        int(query.get("since", ["0"])[0]):]}
            else:
                return self._send(404, b"not found")
            self._send(200, json.dumps(body).encode())

        def log_message(self, *_args):
            pass

    return Handler


def main(argv: list[str]) -> int:
    with open(argv[0]) as fh:
        doc = json.load(fh)
    if doc.get("cores"):
        os.sched_setaffinity(0, doc["cores"])
    state = LogState(fx.LogSpec(**doc["log_spec"]), doc["seed"])
    httpd = ThreadingHTTPServer(("127.0.0.1", 0), make_handler(state))
    httpd.daemon_threads = True
    print(json.dumps({"port": httpd.server_address[1]}), flush=True)
    # The parent closes our stdin to end us; nothing else is read.
    threading.Thread(target=lambda: (sys.stdin.read(), httpd.shutdown()),
                     daemon=True).start()
    httpd.serve_forever(poll_interval=0.2)
    httpd.server_close()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
