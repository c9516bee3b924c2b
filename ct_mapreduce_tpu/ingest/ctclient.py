"""CT log v1 HTTP API client.

Mirrors the reference's use of certificate-transparency-go's
``client.New`` + ``GetSTH`` + ``GetRawEntries``
(/root/reference/cmd/ct-fetch/ct-fetch.go:249-274,416-424):

- entries are fetched in ranges of up to 1000 per request
  (ct-fetch.go:417); the server may return fewer — callers advance by
  what they got, and the client remembers the server's observed page
  size so later windows ask for what the log actually serves (real
  logs cap get-entries far below the spec maximum);
- HTTP 429 AND transient 5xx (500/502/503/504 — real logs shed load
  with these at least as often as with 429) trigger a jittered
  exponential backoff of 500 ms – 5 min and a retry of the same range
  (ct-fetch.go:409-437), honoring Retry-After when present; retries
  are counted under ``ingest.retry.*`` by status;
- other HTTP errors raise and are handled by the caller's
  log-level error policy.

The transport is injectable — ``transport(url) -> (status, headers,
body)`` — so tests and the zero-egress benchmark environment can serve
synthetic logs without sockets. The default, :func:`_default_transport`,
talks ``http.client`` and keeps one connection a (thread, origin) open,
as the reference's ``net/http`` client does: a log's ``get-sth`` and
every ``get-entries`` of its downloader go over one socket (and one TLS
session), a connection the server closed meanwhile is replaced without
a backoff, and what ``http.client`` does not do (a redirect, a proxy
from the environment) is handed, that request alone, to the one-shot
``urllib`` path, :func:`_oneshot_transport`.
"""

from __future__ import annotations

import http.client
import json
import threading
import time
import urllib.error
import urllib.parse
import urllib.request
from dataclasses import dataclass
from typing import Callable, Optional

from ct_mapreduce_tpu.native.leafpack import (
    EntryPage,
    page_of_strings,
    scan_entries,
)
from ct_mapreduce_tpu.telemetry import trace
from ct_mapreduce_tpu.telemetry.metrics import incr_counter, measure
from ct_mapreduce_tpu.utils.backoff import JitteredBackoff

BATCH_SIZE = 1000  # entries per get-entries request (ct-fetch.go:417)

# Statuses retried with backoff instead of raised: rate limiting plus
# the transient 5xx family production logs answer under load.
RETRYABLE_STATUSES = frozenset({429, 500, 502, 503, 504})

Transport = Callable[[str], tuple[int, dict, bytes]]


def short_url(url: str) -> str:
    """Log URL without scheme or trailing slash — the reference's
    ShortURL identity (storage/types.go checkpoint keying)."""
    for prefix in ("https://", "http://"):
        if url.startswith(prefix):
            url = url[len(prefix) :]
            break
    return url.rstrip("/")


_USER_AGENT = "ct-mapreduce-tpu/0.1"
_TIMEOUT_S = 60
_REDIRECTS = frozenset({301, 302, 303, 307, 308})

# What a kept connection raises when the far end closed it while it
# idled or answered; on a connection just made they are the log's own.
_STALE = (ConnectionError, http.client.BadStatusLine,
          http.client.IncompleteRead)


def _oneshot_transport(url: str) -> tuple[int, dict, bytes]:
    """One request through ``urllib``'s opener, a connection of its
    own: it follows redirects and knows the environment's proxies."""
    req = urllib.request.Request(url, headers={"User-Agent": _USER_AGENT})
    try:
        with urllib.request.urlopen(req, timeout=_TIMEOUT_S) as resp:
            return resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as err:
        return err.code, dict(err.headers or {}), err.read()


class _KeptConnections(dict):
    """One thread's open connections by origin. The thread's last
    reference is its ``threading.local`` slot, so they close with it."""

    def __del__(self):
        for conn in self.values():
            conn.close()


_thread = threading.local()


def _proxied(parts: urllib.parse.SplitResult) -> bool:
    """Whether ``urllib`` would send this request to a proxy."""
    return (bool(urllib.request.getproxies().get(parts.scheme))
            and not urllib.request.proxy_bypass(parts.netloc))


def _get(conn: http.client.HTTPConnection, target: str):
    """One ``GET`` and its whole response; a connection that did not
    give one is closed."""
    try:
        conn.request("GET", target, headers={"User-Agent": _USER_AGENT})
        resp = conn.getresponse()
        return resp, resp.read()
    except BaseException:
        conn.close()
        raise


def _default_transport(url: str) -> tuple[int, dict, bytes]:
    """``GET url`` over the calling thread's kept connection to that
    origin, made at the first request; the response is read whole
    before the next request goes out. A kept connection found dead is
    replaced and the request sent once more (``ingest.conn.stale``): a
    server may drop an idle connection at any time, and that is no
    error of the log. Every request counts under ``ingest.conn.opened``
    or ``ingest.conn.reused``, and says which on the caller's open span
    (``fetch.get_entries``' ``reused``)."""
    parts = urllib.parse.urlsplit(url)
    kept = getattr(_thread, "kept", None)
    if kept is None:
        kept = _thread.kept = _KeptConnections()
    origin = (parts.scheme, parts.netloc)
    conn = kept.pop(origin, None)
    if conn is None and (parts.scheme not in ("http", "https")
                         or _proxied(parts)):
        return _oneshot_transport(url)
    target = (parts.path or "/") + ("?" + parts.query if parts.query else "")
    reused = conn is not None
    if reused:
        try:
            resp, body = _get(conn, target)
        except _STALE:
            incr_counter("ingest", "conn", "stale")
            reused = False
    if not reused:
        make = (http.client.HTTPSConnection if parts.scheme == "https"
                else http.client.HTTPConnection)
        conn = make(parts.hostname, parts.port, timeout=_TIMEOUT_S)
        incr_counter("ingest", "conn", "opened")
        resp, body = _get(conn, target)
    else:
        incr_counter("ingest", "conn", "reused")
    trace.annotate(reused=int(reused))
    if resp.will_close:
        conn.close()
    else:
        kept[origin] = conn
    if resp.status in _REDIRECTS and resp.headers.get("Location"):
        return _oneshot_transport(url)
    return resp.status, dict(resp.headers), body


@dataclass
class SignedTreeHead:
    tree_size: int
    timestamp_ms: int
    sha256_root_hash: str = ""
    tree_head_signature: str = ""


@dataclass
class RawEntry:
    index: int
    leaf_input: str  # base64, as served
    extra_data: str


def _parse_entries(body, _asked: int, sp) -> list[dict]:
    """A get-entries body as the JSON parser's list of entry objects."""
    entries = json.loads(body).get("entries", [])
    sp.set(n=len(entries))
    return entries


def _parse_page(body, asked: int, sp) -> EntryPage:
    """A get-entries body as an :class:`EntryPage`: by the native scan
    where it takes the bytes, else by ``json.loads``."""
    if not isinstance(body, bytes):  # an injected transport's str
        body = body.encode() if isinstance(body, str) else bytes(body)
    page = scan_entries(body, asked)
    scanned = page is not None
    if not scanned:
        entries = json.loads(body).get("entries", [])
        page = page_of_strings(
            [e["leaf_input"] for e in entries],
            [e.get("extra_data", "") for e in entries])
    incr_counter("ingest", "page", "scanned" if scanned else "json_fallback")
    sp.set(n=len(page), scanned=int(scanned))
    return page


class CTClientError(RuntimeError):
    def __init__(self, url: str, status: int, body: bytes):
        super().__init__(f"HTTP {status} from {url}: {body[:200]!r}")
        self.status = status


class CTLogClient:
    """One CT log endpoint, normalized to ``https://`` when no scheme
    is given (the reference's config takes full URLs)."""

    def __init__(
        self,
        log_url: str,
        transport: Optional[Transport] = None,
        sleep: Callable[[float], None] = time.sleep,
        max_retries: int = 100,
    ):
        if "://" not in log_url:
            log_url = "https://" + log_url
        self.log_url = log_url.rstrip("/")
        self.short_url = short_url(log_url)
        self.transport = transport or _default_transport
        self.sleep = sleep
        self.max_retries = max_retries
        # Adaptive get-entries window: starts at the spec maximum and
        # clamps down to the page size the server actually returns.
        self.page_size = BATCH_SIZE

    # -- plumbing --------------------------------------------------------
    def _get_json(self, path: str) -> dict:
        return json.loads(self._get_body(path)[0])

    def _get_body(self, path: str) -> tuple[bytes, int]:
        """The 200 response's body and the attempts it took; retries
        and their sleeps happen in here."""
        url = f"{self.log_url}/ct/v1/{path}"
        backoff = JitteredBackoff(min_s=0.5, max_s=300.0)
        status = 429
        for attempt in range(1, self.max_retries + 1):
            status, headers, body = self.transport(url)
            if status == 200:
                return body, attempt
            if status in RETRYABLE_STATUSES:
                # ct-fetch.go:426-437: jittered 500ms-5min, honor
                # Retry-After seconds when the server sends one. 5xx
                # takes the exact same lane — a 503 from an overloaded
                # log is rate limiting by another name.
                if status == 429:
                    incr_counter("LogWorker", self.short_url, "429")
                incr_counter("ingest", "retry", str(status))
                retry_after = next(
                    (v for k, v in headers.items()
                     if k.lower() == "retry-after"),
                    None,
                )
                if retry_after:
                    try:
                        # Clamp to the 500ms-5min window — a hostile value
                        # must neither stall the downloader for hours nor
                        # turn the retry loop into a zero-delay hammer.
                        delay = min(max(float(retry_after), backoff.min_s),
                                    backoff.max_s)
                    except ValueError:
                        delay = backoff.duration()
                else:
                    delay = backoff.duration()
                self.sleep(delay)
                continue
            raise CTClientError(url, status, body)
        incr_counter("ingest", "retry", "giveup")
        raise CTClientError(url, status, b"retry budget exhausted")

    # -- API -------------------------------------------------------------
    def get_sth(self) -> SignedTreeHead:
        with measure("LogWorker", self.short_url, "getSTH"):
            obj = self._get_json("get-sth")
        return SignedTreeHead(
            tree_size=int(obj["tree_size"]),
            timestamp_ms=int(obj.get("timestamp", 0)),
            sha256_root_hash=obj.get("sha256_root_hash", ""),
            tree_head_signature=obj.get("tree_head_signature", ""),
        )

    def _get_entries(self, start: int, end: int, parse):
        """The response for ``[start, end]``, cut to this client's
        window, as ``parse(body, asked, span)`` reads it (anything with
        a length: the entry count). The ``getRawEntries`` timer keeps
        what it always held (socket wait, body read and parse); the two
        spans divide it. The first truncated response clamps the window
        to the page size the server demonstrated, so every later request
        asks for exactly what the log serves instead of re-discovering
        the cap one oversized range at a time."""
        end = min(end, start + self.page_size - 1)
        asked = end - start + 1
        with measure("LogWorker", self.short_url, "getRawEntries"):
            with trace.span("fetch.get_entries", cat="fetch") as sp:
                body, attempts = self._get_body(
                    f"get-entries?start={start}&end={end}")
                sp.set(bytes=len(body), attempts=attempts)
            with trace.span("fetch.parse_json", cat="fetch") as sp:
                entries = parse(body, asked, sp)
        if 0 < len(entries) < asked:
            # Short page on a full-window ask: adopt the server's size.
            self.page_size = len(entries)
            incr_counter("ingest", "window_clamp")
        return entries

    def get_raw_entries(self, start: int, end: int) -> list[RawEntry]:
        """Entries ``[start, end]`` inclusive, like ct-go's
        GetRawEntries, one object an entry; the server may truncate the
        range (see :meth:`_get_entries`)."""
        if end < start:
            return []
        entries = self._get_entries(start, end, _parse_entries)
        with trace.span("fetch.parse_json", cat="fetch", n=len(entries)):
            return [
                RawEntry(
                    index=start + i,
                    leaf_input=e["leaf_input"],
                    extra_data=e.get("extra_data", ""),
                )
                for i, e in enumerate(entries)
            ]

    def get_entry_page(self, start: int, end: int) -> EntryPage:
        """The same request for the raw-batch path: the response stays
        the bytes the transport returned and a native scan, GIL
        released, finds where each entry's two base64 values lie in
        them; entry ``start + i`` is the page's i-th. Whether a page is
        scanned is the scanner's answer about these bytes (an escape, a
        byte outside ASCII, an odd member, a library without it: no),
        and a page it does not take is parsed with ``json.loads`` as
        :meth:`get_raw_entries` does, raising what that raises, and laid
        out in the same form. ``ingest.page.scanned`` and
        ``ingest.page.json_fallback`` count which, a page each."""
        if end < start:
            return page_of_strings([], [])
        return self._get_entries(start, end, _parse_page)

    def get_entry_and_proof(self, index: int, tree_size: int) -> dict:
        """ct-getcert's fetch path (get-entry-and-proof)."""
        return self._get_json(
            f"get-entry-and-proof?leaf_index={index}&tree_size={tree_size}"
        )
