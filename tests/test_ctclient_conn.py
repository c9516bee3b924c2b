"""The default transport against a real socket: one connection a
(thread, origin), replaced without a backoff when the server dropped
it, and the one-shot ``urllib`` path for what ``http.client`` does not
do. The server is an in-process ``ThreadingHTTPServer`` in front of a
:class:`FakeLog`, scripted by the attributes of :class:`Front`."""

from __future__ import annotations

import base64
import contextlib
import http.client
import socket
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest
from fakelog import FakeLog

from ct_mapreduce_tpu.ingest import ctclient
from ct_mapreduce_tpu.ingest.ctclient import CTClientError, CTLogClient
from ct_mapreduce_tpu.telemetry import metrics, trace

PAGE = 8
PAGES = 20


class Front:
    """What the server does besides answering as the log would."""

    def __init__(self):
        self.log = FakeLog()
        self.log.max_batch = PAGE
        for i in range(PAGE * PAGES):
            self.log.entries.append({
                "leaf_input": base64.b64encode(b"leaf-%05d" % i * 9).decode(),
                "extra_data": base64.b64encode(b"chain-%05d" % i * 5).decode(),
            })
        self.protocol = "HTTP/1.1"
        self.close_header = False  # answer `Connection: close`
        self.close_every = 0  # drop the connection after every k-th response
        self.not_found_hits = 0  # answer this many 404s with a body
        self.truncate_hits = 0  # die halfway through this many bodies
        self.lock = threading.Lock()
        self.accepted = 0
        self.closed = 0
        self.responses = 0
        self.sockets: list[socket.socket] = []
        self.by_connection: dict[int, list[str]] = {}

    def drop_idle_connections(self) -> None:
        """What a front end does to connections idle too long."""
        with self.lock:
            for sock in self.sockets:
                with contextlib.suppress(OSError):
                    sock.shutdown(socket.SHUT_RDWR)


def _handler(front: Front):
    class Handler(BaseHTTPRequestHandler):
        protocol_version = front.protocol

        def setup(self):
            super().setup()
            with front.lock:
                front.accepted += 1
                self.number = front.accepted
                front.sockets.append(self.connection)
                front.by_connection[self.number] = []

        def finish(self):
            super().finish()
            with front.lock:
                front.closed += 1
                front.sockets.remove(self.connection)

        def do_GET(self):  # noqa: N802
            with front.lock:
                front.by_connection[self.number].append(self.path)
                front.responses += 1
                drop = (front.close_every
                        and front.responses % front.close_every == 0)
                not_found = front.not_found_hits > 0
                front.not_found_hits -= not_found
                truncate = not not_found and front.truncate_hits > 0
                front.truncate_hits -= truncate
            if self.path.startswith("/old/"):
                self.send_response(302)
                self.send_header("Location", "/new/" + self.path[5:])
                self.send_header("Content-Length", "5")
                self.end_headers()
                self.wfile.write(b"moved")
                return
            if not_found:
                status, headers, body = 404, {}, b"no such log here"
            else:
                status, headers, body = front.log.transport(
                    "http://front" + self.path)
            self.send_response(status)
            for k, v in headers.items():
                self.send_header(k, v)
            self.send_header("Content-Length", str(len(body)))
            if front.close_header:
                self.send_header("Connection", "close")
            self.end_headers()
            if truncate:
                self.wfile.write(body[:len(body) // 2])
                self.close_connection = True
                return
            self.wfile.write(body)
            if drop:
                self.close_connection = True  # no header says so

        def log_message(self, *_args):
            pass

    return Handler


@pytest.fixture
def front():
    """A scripted log front on a loopback port; ``front.url`` reaches
    it. The handler class is made at the first request's accept, so a
    test may set ``front.protocol`` before it connects."""
    front = Front()

    class Server(ThreadingHTTPServer):
        daemon_threads = True

        def finish_request(self, request, client_address):
            _handler(front)(request, client_address, self)

    server = Server(("127.0.0.1", 0), BaseHTTPRequestHandler)
    front.url = f"http://127.0.0.1:{server.server_address[1]}"
    thread = threading.Thread(target=server.serve_forever, args=(0.02,),
                              daemon=True)
    thread.start()
    try:
        yield front
    finally:
        server.shutdown()
        server.server_close()
        thread.join(timeout=10)
        assert not thread.is_alive()


@pytest.fixture(autouse=True)
def fresh_sink_and_thread():
    """Counters from zero, the tracer on, and no connection left on the
    test's thread by the test before."""
    saved = metrics.get_sink()
    metrics.set_sink(metrics.InMemSink())
    trace.disable()
    trace.enable()
    vars(ctclient._thread).pop("kept", None)
    yield
    vars(ctclient._thread).pop("kept", None)
    trace.disable()
    metrics.set_sink(saved)


def counters(prefix: str = "ingest.") -> dict:
    return {k: int(v) for k, v in
            metrics.get_sink().snapshot()["counters"].items()
            if k.startswith(prefix)}


def no_sleep(delay):
    raise AssertionError(f"slept {delay} s")


def expected_page(front: Front, k: int) -> tuple[list, list]:
    rows = front.log.entries[k * PAGE:(k + 1) * PAGE]
    return ([r["leaf_input"].encode() for r in rows],
            [r["extra_data"].encode() for r in rows])


def fetch_all(client: CTLogClient, front: Front, between=None) -> None:
    assert client.get_sth().tree_size == PAGE * PAGES
    for k in range(PAGES):
        if between is not None:
            between(k)
        page = client.get_entry_page(k * PAGE, k * PAGE + PAGE - 1)
        assert tuple(page.items()) == expected_page(front, k), k


def get_entries_spans() -> list[dict]:
    return [e["args"] for e in trace.snapshot_events()
            if e["ph"] == "X" and e["name"] == "fetch.get_entries"]


def test_one_connection_a_log(front):
    """(a) get-sth and twenty pages: one accepted connection."""
    fetch_all(CTLogClient(front.url, sleep=no_sleep), front)
    assert front.accepted == 1
    assert counters("ingest.conn.") == {
        "ingest.conn.opened": 1, "ingest.conn.reused": PAGES}
    spans = get_entries_spans()
    assert [s["reused"] for s in spans] == [1] * PAGES
    assert all(s["attempts"] == 1 and s["bytes"] > 0 for s in spans)


def test_the_body_is_the_servers_bytes(front):
    url = f"{front.url}/ct/v1/get-entries?start=0&end={PAGE - 1}"
    want = front.log.transport(url)[2]
    for _ in range(3):
        status, headers, body = ctclient._default_transport(url)
        assert (status, body) == (200, want)
        assert headers["Content-Length"] == str(len(want))
    assert front.accepted == 1


@pytest.mark.parametrize("close_every", [1, 3, 7])
def test_a_dropped_connection_is_replaced_in_silence(front, close_every):
    """(b) the server closes after every k-th response, with no header
    to say so, and once more while the client idles."""
    front.close_every = close_every

    def between(k):
        if k == PAGES // 2:
            front.drop_idle_connections()
            time.sleep(0.05)

    fetch_all(CTLogClient(front.url, sleep=no_sleep), front, between)
    got = counters()
    # A drop is noticed by the request after it: the responses before
    # the last that were a k-th, and the idle one if a connection stood.
    stale = PAGES // close_every + ((PAGES // 2 + 1) % close_every != 0)
    assert got["ingest.conn.stale"] == stale
    assert got["ingest.conn.opened"] == 1 + stale == front.accepted
    assert (got["ingest.conn.opened"] + got.get("ingest.conn.reused", 0)
            == PAGES + 1)
    assert not [k for k in got if k.startswith("ingest.retry.")]
    assert all(s["attempts"] == 1 for s in get_entries_spans())
    # Every request was answered once, or met a closed socket first.
    paths = [p for c in sorted(front.by_connection)
             for p in front.by_connection[c]]
    assert len(paths) == PAGES + 1 and len(set(paths)) == PAGES + 1


@pytest.mark.parametrize("how", ["http10", "close_header"])
def test_a_server_that_keeps_no_connection(front, how):
    """(c) works, a connection a request, none of them stale."""
    if how == "http10":
        front.protocol = "HTTP/1.0"
    else:
        front.close_header = True
    fetch_all(CTLogClient(front.url, sleep=no_sleep), front)
    assert front.accepted == PAGES + 1
    got = counters("ingest.conn.")
    assert got == {"ingest.conn.opened": PAGES + 1}
    assert [s["reused"] for s in get_entries_spans()] == [0] * PAGES


@pytest.mark.parametrize("status", [429, 503])
def test_backoff_over_the_kept_connection(front, status):
    """(d) the sleeps and counters an injected transport sees
    (test_ingest.py::test_client_429_backoff_and_retry_after,
    test_client_5xx_backoff_same_lane), and the connection survives."""
    hits = "rate_limit_hits" if status == 429 else "server_error_hits"
    front.log.server_error_status = 503
    sleeps = []
    client = CTLogClient(front.url, sleep=sleeps.append)
    setattr(front.log, hits, 2)
    front.log.retry_after = "3"
    assert client.get_sth().tree_size == PAGE * PAGES
    assert sleeps == [3.0, 3.0]
    setattr(front.log, hits, 1)
    front.log.retry_after = None
    sleeps.clear()
    page = client.get_entry_page(0, PAGE - 1)
    assert tuple(page.items()) == expected_page(front, 0)
    assert len(sleeps) == 1 and 0 < sleeps[0] <= 300.0
    got = counters()
    assert got[f"ingest.retry.{status}"] == 3
    assert front.accepted == 1 and got["ingest.conn.opened"] == 1
    assert got["ingest.conn.reused"] == 4 and "ingest.conn.stale" not in got
    (span,) = get_entries_spans()
    assert span["attempts"] == 2 and span["reused"] == 1


def test_an_error_body_leaves_the_connection_usable(front):
    """(e) a 404 with a body raises; the next request goes over the
    same connection."""
    client = CTLogClient(front.url, sleep=no_sleep)
    front.not_found_hits = 1
    with pytest.raises(CTClientError) as err:
        client.get_sth()
    assert err.value.status == 404 and "no such log here" in str(err.value)
    assert client.get_sth().tree_size == PAGE * PAGES
    assert front.accepted == 1
    assert counters("ingest.conn.") == {
        "ingest.conn.opened": 1, "ingest.conn.reused": 1}


def test_a_redirect_is_followed(front):
    """(f) by the one-shot path, that request alone."""
    client = CTLogClient(front.url + "/old", sleep=no_sleep)
    assert client.get_sth().tree_size == PAGE * PAGES
    page = client.get_entry_page(0, PAGE - 1)
    assert tuple(page.items()) == expected_page(front, 0)
    sth = "ct/v1/get-sth"
    entries = f"ct/v1/get-entries?start=0&end={PAGE - 1}"
    # The kept connection carried both 302s; urllib starts from the
    # same URL (its handler judges the Location), a connection a hop.
    assert [front.by_connection[c] for c in sorted(front.by_connection)] == [
        ["/old/" + sth, "/old/" + entries],
        ["/old/" + sth], ["/new/" + sth],
        ["/old/" + entries], ["/new/" + entries]]
    assert counters("ingest.conn.") == {
        "ingest.conn.opened": 1, "ingest.conn.reused": 1}


def test_a_proxy_in_the_environment_takes_the_one_shot_path(
        front, monkeypatch):
    port = front.url.rsplit(":", 1)[1]
    monkeypatch.setenv("http_proxy", front.url)
    monkeypatch.setenv("no_proxy", "")
    # urllib reads the environment when it builds its opener, once.
    monkeypatch.setattr(urllib.request, "_opener", None)
    # The "proxy" is the front itself: it sees the absolute URL.
    status, _headers, body = ctclient._default_transport(
        "http://log.example:1/ct/v1/get-sth")
    assert status == 200 and b"tree_size" in body
    assert front.by_connection[1] == ["http://log.example:1/ct/v1/get-sth"]
    assert counters("ingest.conn.") == {}
    monkeypatch.setenv("no_proxy", "127.0.0.1")
    assert ctclient._default_transport(
        f"http://127.0.0.1:{port}/ct/v1/get-sth")[0] == 200
    assert counters("ingest.conn.") == {"ingest.conn.opened": 1}


def test_two_threads_two_connections(front):
    """(g) a connection belongs to its thread, and ends with it."""
    errors = []
    barrier = threading.Barrier(2)

    def run(prefix):
        try:
            client = CTLogClient(f"{front.url}/{prefix}", sleep=no_sleep)
            barrier.wait(timeout=10)
            fetch_all(client, front)
        except BaseException as err:  # the test reads it below
            errors.append(err)

    threads = [threading.Thread(target=run, args=(p,)) for p in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    assert not errors
    assert front.accepted == 2
    for paths in front.by_connection.values():
        assert len(paths) == PAGES + 1
        assert len({p.split("/")[1] for p in paths}) == 1  # one thread's
        starts = [int(p.split("start=")[1].split("&")[0]) for p in paths[1:]]
        assert starts == sorted(starts)
    assert counters("ingest.conn.") == {
        "ingest.conn.opened": 2, "ingest.conn.reused": 2 * PAGES}
    # The threads are gone, and their sockets with them.
    deadline = time.monotonic() + 10
    while front.closed < 2 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert front.closed == 2


def test_a_fresh_connection_that_dies_mid_body_raises(front):
    """(h) the log's error, not a stale connection: raised, once."""
    front.truncate_hits = 1
    client = CTLogClient(front.url, sleep=no_sleep)
    with pytest.raises(http.client.IncompleteRead):
        client.get_sth()
    assert front.accepted == 1
    assert counters("ingest.conn.") == {"ingest.conn.opened": 1}
    assert client.get_sth().tree_size == PAGE * PAGES  # a new connection
    assert front.accepted == 2


def test_a_kept_connection_that_dies_mid_body_is_tried_once_more(front):
    client = CTLogClient(front.url, sleep=no_sleep)
    assert client.get_sth().tree_size == PAGE * PAGES
    front.truncate_hits = 1
    page = client.get_entry_page(0, PAGE - 1)
    assert tuple(page.items()) == expected_page(front, 0)
    assert counters("ingest.conn.") == {
        "ingest.conn.opened": 2, "ingest.conn.stale": 1}
    front.truncate_hits = 2  # the replacement dies too: the log's error
    with pytest.raises(http.client.IncompleteRead):
        client.get_entry_page(PAGE, 2 * PAGE - 1)


def test_no_listener_raises_as_a_refused_connection():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    with pytest.raises(ConnectionRefusedError):
        CTLogClient(f"http://127.0.0.1:{port}", sleep=no_sleep).get_sth()
    assert counters("ingest.conn.") == {"ingest.conn.opened": 1}
