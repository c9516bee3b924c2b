"""The query plane's HTTP surface: a batched membership oracle as a
stdlib JSON API (``queryPort`` directive).

Endpoints:

- ``POST /query`` — one or many membership questions. Body is either a
  single query object or ``{"queries": [...]}``; each query is
  ``{"issuer": <issuerID>, "expDate": <expDate id>, "serial": <hex>}``
  (issuerID = base64url(SHA-256(SPKI)), expDate in the report formats
  ``2031-06-15`` / ``2031-06-15-14``, serial as hex content bytes).
  Optional ``"timeoutMs"`` is the request deadline. The response
  carries per-query ``known`` flags plus the answering view's
  ``epoch`` and ``staleness_s`` — a consumer always knows HOW current
  the answer is. Overload is an explicit ``429 overloaded``; a missed
  deadline is ``504 deadline_exceeded``.
- ``GET /issuer/<issuerID>`` — per-issuer metadata (running unknown
  total, CRL/DN set sizes) from the same pinned view.
- ``GET /healthz`` — queue depth vs cap, snapshot age/epoch, shed
  total: the numbers that distinguish "keeping up" from "shedding".
- ``GET /getcert?log=<url>&index=<n>`` — serving-plane proxy for the
  ``ct-getcert`` flow: the server (which already holds log
  credentials/limits) fetches one entry and returns its PEM, so edge
  clients need no direct log access.

One front thread (:class:`_Front`, ``query-front``): a ``selectors``
loop accepts, reads, answers and closes every connection, so a query
costs no thread's life and no ``http.server`` parse. ``POST /query``
never blocks it (the batcher calls back, the loop writes the answer);
the ``GET`` routes above, which can block or stream, run on a small
fixed pool. The protocol is HTTP/1.0-style: the answer closes the
connection unless the client sent ``Connection: keep-alive``.

The oracle half (:class:`MembershipOracle`) is independent of HTTP —
tests drive it in-process — and composes the
three serving primitives, hottest first:
:class:`~ct_mapreduce_tpu.serve.cache.HotSerialCache` (memoized
answers, epoch-floor validated), :class:`~ct_mapreduce_tpu.serve.
batcher.MicroBatcher` (dynamic batching + admission control), and
:class:`~ct_mapreduce_tpu.serve.snapshot.ReplicaPool` (round-robin
epoch-pinned device views with staggered refresh and automatic host
fallback). ``serveReplicas`` / ``serveDevice`` / ``serveCacheSize``
directives (and their ``CTMR_SERVE_*`` env equivalents) tune the tier.
"""

from __future__ import annotations

import errno
import json
import os
import selectors
import socket
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from http import HTTPStatus
from typing import Optional

import numpy as np

from ct_mapreduce_tpu.config import profile as platprofile
from ct_mapreduce_tpu.core.types import ExpDate
from ct_mapreduce_tpu.serve.batcher import (
    DeadlineExceeded,
    MicroBatcher,
    Overloaded,
)
from ct_mapreduce_tpu.serve.cache import HotSerialCache
from ct_mapreduce_tpu.serve.snapshot import ReplicaPool
from ct_mapreduce_tpu.telemetry import trace
from ct_mapreduce_tpu.telemetry.metrics import add_sample, incr_counter


_SERVE_KNOBS = (
    platprofile.Knob("serveReplicas", "CTMR_SERVE_REPLICAS", 2,
                     parse=int, is_set=platprofile.pos_int,
                     post=lambda v: int(v)),
    platprofile.Knob("serveDevice", "CTMR_SERVE_DEVICE", True,
                     parse=platprofile.parse_bool_lenient,
                     env_is_set=platprofile.any_set, post=bool),
    platprofile.Knob("serveCacheSize", "CTMR_SERVE_CACHE_SIZE", 4096,
                     parse=int, is_set=platprofile.nonzero_int,
                     post=lambda v: max(0, int(v))),
)


def resolve_serve(replicas: int = 0, device: Optional[bool] = None,
                  cache_size: int = 0) -> tuple[int, bool, int]:
    """Resolve the serving-tier knobs through the shared
    platformProfile ladder (config/profile.py): explicit value (config
    directive / kwarg) > ``CTMR_SERVE_REPLICAS`` /
    ``CTMR_SERVE_DEVICE`` / ``CTMR_SERVE_CACHE_SIZE`` env > profile
    ``knobs.serve`` > defaults (2 replicas; device serving with
    automatic host fallback; 4096-entry hot-serial cache).
    ``cache_size < 0`` disables the cache; unparseable env values are
    ignored, matching the config layer's tolerance."""
    r = platprofile.resolve_section("serve", _SERVE_KNOBS, {
        "serveReplicas": int(replicas or 0),
        "serveDevice": device,
        "serveCacheSize": int(cache_size or 0),
    })
    return (r["serveReplicas"], r["serveDevice"], r["serveCacheSize"])


def resolve_filter_first(flag=None) -> bool:
    """Serve-plane filter-first tier: explicit value > the
    ``CTMR_SERVE_FILTER_FIRST`` env > off."""
    if flag is not None:
        return bool(flag)
    return os.environ.get("CTMR_SERVE_FILTER_FIRST", "").strip().lower() \
        in ("1", "t", "true")


class FilterTier:
    """An epoch-tagged filter-cascade snapshot in front of the table
    tier (round 15): compiled from the aggregator's filter capture,
    it answers NEGATIVE lookups without touching a table view — exact
    for every serial the build-time state knew — and forwards
    positives to the table-confirm tier, which kills the cascade's
    false positives. Serials first seen AFTER the build answer through
    the same epoch/staleness surface the replica pool already reports:
    the tier's epoch is the pool's floor epoch at build time, and
    consumers read ``staleness_s`` exactly as they do for views."""

    def __init__(self, artifact, issuer_ids: list[str], epoch: int):
        self.artifact = artifact
        # Registry snapshot: run-local issuer index → issuerID, as of
        # the build. Queries for indices past this snapshot (issuers
        # first seen after the build) must FORWARD to the table, not
        # answer negative from a filter that predates them.
        self.issuer_ids = issuer_ids
        self.epoch = int(epoch)
        self.created_wall = time.time()

    @classmethod
    def build(cls, agg, fp_rate: float, epoch: int,
              cache=None) -> "FilterTier":
        """``cache`` (a :class:`filter.cache.GroupBuildCache`) arms the
        CTMRFL02 dirty-group path: across refresh ticks only churned
        groups rebuild (the oracle owns one cache for its lifetime)."""
        from ct_mapreduce_tpu.filter import build_from_aggregator

        art = build_from_aggregator(agg, fp_rate=fp_rate, cache=cache)
        ids = [agg.registry.issuer_at(i).id()
               for i in range(len(agg.registry))]
        return cls(art, ids, epoch)

    def age_s(self) -> float:
        return max(0.0, time.time() - self.created_wall)

    def negatives(self, items: list) -> np.ndarray:
        """bool[n]: lanes the cascade answers *excluded* — definitely
        unknown as of the build. False means forward to the table
        (cascade-positive, or outside the build's registry snapshot)."""
        n = len(items)
        out = np.zeros((n,), bool)
        by_group: dict = {}
        for i, (idx, eh, _sb) in enumerate(items):
            if 0 <= int(idx) < len(self.issuer_ids):
                key = (self.issuer_ids[int(idx)], int(eh))
                by_group.setdefault(key, []).append(i)
            # idx == -1 (registry never saw the issuer): the TABLE is
            # the authority on honest-false; forward.
        with trace.span("serve.filter", cat="serve", lanes=n):
            for (iss, eh), lanes in by_group.items():
                g = self.artifact.group_for(iss, eh)
                if g is None:
                    # No serials for this (issuer, expDate) at build
                    # time: exact-negative for the build corpus.
                    out[lanes] = True
                    continue
                hit = self.artifact.query_group(
                    g, [items[i][2] for i in lanes])
                out[np.asarray(lanes)[~hit]] = True
        return out


class Admitted:
    """A query between :meth:`MembershipOracle.admit` and the
    ``query_raw`` that finishes it: the lanes the cache and the filter
    tier answered (``out``), those that wait for the table (``miss``,
    indices into ``items``) and their place in the batcher's queue."""

    __slots__ = ("items", "out", "miss", "request")

    def __init__(self, items: list, out: list, miss: list) -> None:
        self.items = items
        self.out = out
        self.miss = miss
        self.request = None

    @property
    def lanes(self) -> int:
        """Lanes in the batcher's queue: 0 says nothing waits."""
        return len(self.miss) if self.request is not None else 0


class MembershipOracle:
    """Batched "is serial S known for (issuer, expDate)?" over a live
    aggregator: a hot-serial result cache in front of dynamic batching
    in front of a round-robin pool of epoch-pinned device replicas
    (host-numpy fallback when no device copy can pin). With
    ``filter_first`` (round 15), a filter-cascade tier sits between
    the cache and the batcher: cascade-negative lanes answer without a
    table view, cascade-positive lanes fall through for table
    confirmation."""

    def __init__(
        self,
        agg,
        max_batch: int = 4096,
        max_delay_s: float = 0.002,
        max_queue_lanes: int = 1 << 16,
        max_staleness_s: float = 1.0,
        device: Optional[bool] = None,
        replicas: int = 0,
        cache_size: int = 0,
        filter_first: Optional[bool] = None,
        filter_fp_rate: float = 0.0,
        distrib_history: int = 0,
        max_delta_chain: int = 0,
    ) -> None:
        self._agg = agg
        replicas, device, cache_size = resolve_serve(
            replicas, device, cache_size)
        self.snapshots = ReplicaPool(
            agg, n_replicas=replicas, max_staleness_s=max_staleness_s,
            device=device)
        self.cache = (HotSerialCache(cache_size)
                      if cache_size > 0 else None)
        self.batcher = MicroBatcher(
            self._run_batch, max_batch=max_batch, max_delay_s=max_delay_s,
            max_queue_lanes=max_queue_lanes)
        # Filter-first tier (round 15): built lazily on the first
        # refresh (construction must not fail when the aggregator has
        # no capture yet — the tier simply stays cold and every lane
        # takes the table path).
        from ct_mapreduce_tpu.filter import DEFAULT_FP_RATE

        self.filter_first = resolve_filter_first(filter_first)
        self.filter_fp_rate = float(filter_fp_rate) or DEFAULT_FP_RATE
        self.filter_tier: Optional[FilterTier] = None
        # Epoch-persistent build cache (CTMRFL02): refresh ticks reuse
        # clean groups' cascades verbatim, so the steady-state refresh
        # costs O(churn). Harmless for fl01 (the builder ignores it).
        from ct_mapreduce_tpu.filter import GroupBuildCache

        self.filter_build_cache = GroupBuildCache()
        # Distribution store (round 18): published epochs, delta
        # links, containers, pre-compressed variants — what the
        # /filter* CDN routes serve. Armed alongside the filter tier.
        self.distributor = None
        if self.filter_first:
            from ct_mapreduce_tpu.distrib import (
                FilterDistributor,
                resolve_distrib,
            )

            history, max_chain = resolve_distrib(
                distrib_history, max_delta_chain)
            self.distributor = FilterDistributor(
                history=history, max_chain=max_chain)
        if self.filter_first and getattr(
                agg, "filter_capture", None) is not None:
            try:
                self.refresh_filter()
            except Exception:
                pass  # serve must come up; refresh_filter can retry

    def refresh_filter(self, fp_rate: float = 0.0) -> FilterTier:
        """(Re)build the filter tier from the live aggregator's
        capture, tagged with the replica pool's current floor epoch.
        The rebuilt artifact also publishes into the distribution
        store (source ``local`` — a leader-fed merged artifact
        outranks it). Raises ``ValueError`` when the aggregator has no
        capture."""
        tier = FilterTier.build(
            self._agg, float(fp_rate) or self.filter_fp_rate,
            self.snapshots.floor_epoch(),
            cache=self.filter_build_cache)
        self.filter_tier = tier
        if self.distributor is not None:
            self.distributor.publish(
                tier.epoch, tier.artifact.to_bytes(), source="local")
        incr_counter("serve", "filter_refresh")
        return tier

    def publish_artifact(self, epoch: int, blob: bytes,
                         source: str = "fleet") -> bool:
        """Publish externally built artifact bytes (the fleet leader's
        merged filter, fanned out on epoch ticks) into this worker's
        distribution store. Byte-identical input on every worker ⇒
        identical ETags/deltas/containers fleet-wide."""
        if self.distributor is None:
            return False
        return self.distributor.publish(epoch, blob, source=source)

    def _run_batch(self, items: list) -> list:
        view = self.snapshots.view()
        # The view that answers, on the batcher's serve.batch too: its
        # epoch is a snapshot.capture's, so a batch joins its capture.
        answered_by = {"epoch": view.epoch,
                       "age_ms": round(view.age_s() * 1e3, 3)}
        trace.annotate(**answered_by)
        with trace.span(
                "serve.lookup", cat="serve", lanes=len(items),
                device=int(view._device),
                replica=(-1 if view.replica_ix is None
                         else int(view.replica_ix)), **answered_by):
            known = view.lookup(items)
        age = view.age_s()
        return [(bool(k), view.epoch, age) for k in known]

    def _before_the_table(self, items: list) -> "Admitted":
        """What needs no table view: the cache answers what it holds
        (valid while an entry's epoch >= the pool's floor — equivalent
        to the round-robin picking the stalest replica), the filter
        tier when armed answers its negatives (at the tier's epoch)."""
        n = len(items)
        out: list = [None] * n
        if self.cache is None:
            miss = list(range(n))
        else:
            floor = self.snapshots.floor_epoch()
            now = time.time()
            miss = []
            for i, it in enumerate(items):
                e = self.cache.get(it, floor)
                if e is None:
                    miss.append(i)
                else:
                    out[i] = (e.known, e.epoch,
                              max(0.0, now - e.created_wall))
            if n - len(miss):
                incr_counter("serve", "cache_hit",
                             value=float(n - len(miss)))
            if miss:
                incr_counter("serve", "cache_miss", value=float(len(miss)))
        tier = self.filter_tier if self.filter_first else None
        if tier is not None and miss:
            neg = tier.negatives([items[i] for i in miss])
            age = tier.age_s()
            fwd = []
            for j, i in enumerate(miss):
                if neg[j]:
                    out[i] = (False, tier.epoch, age)
                else:
                    fwd.append(i)
            if len(miss) - len(fwd):
                incr_counter("serve", "filter_negative",
                             value=float(len(miss) - len(fwd)))
            if fwd:
                incr_counter("serve", "filter_forward",
                             value=float(len(fwd)))
            miss = fwd
        return Admitted(items, out, miss)

    def admit(self, items: list, timeout_s: Optional[float],
              answered) -> "Admitted":
        """:meth:`query_raw` up to its wait: the cache and the filter
        tier answer what they can and the rest is admitted to the
        batcher (:class:`Overloaded` raises here). ``answered()`` is
        then called once, on the batcher's thread, when ``query_raw``
        of what this returns will not wait; never where no lane needed
        the table (``lanes`` 0: ``query_raw`` may be called at once)."""
        asked = self._before_the_table(items)
        if asked.miss:
            asked.request = self.batcher.admit(
                [items[i] for i in asked.miss], timeout_s, answered)
        return asked

    def query_raw(self, items, timeout_s: Optional[float] = None) -> list:
        """items: [(issuer_idx, exp_hour, serial_bytes)] →
        [(known, epoch, staleness_s)]: the cache, the filter tier, and
        the rest batched through the oracle, each sub-batch answered by
        ONE pinned view. Blocks until the batch ran — unless ``items``
        is what :meth:`admit` returned for them and ``answered`` was
        called: the query front's loop asks in those two halves and
        waits in neither. (The second half is this method and no other,
        so that every answer of the plane still leaves through
        ``query_raw``: what wraps it sees them all.)"""
        asked = (items if isinstance(items, Admitted)
                 else self._before_the_table(items))
        if not asked.miss:
            return asked.out
        sub = [asked.items[i] for i in asked.miss]
        res = (self.batcher.submit(sub, timeout_s=timeout_s)
               if asked.request is None else asked.request.results())
        done = time.time()
        for i, it, r in zip(asked.miss, sub, res):
            asked.out[i] = r
            if self.cache is not None:
                self.cache.put(it, known=r[0], epoch=r[1],
                               created_wall=done - r[2])
        return asked.out

    def resolve_issuer(self, issuer_id: str) -> int:
        idx = self._agg.registry.index_of_issuer_id(issuer_id)
        return -1 if idx is None else idx

    def issuer_meta(self, issuer_id: str) -> Optional[dict]:
        view = self.snapshots.view()
        meta = view.issuer_meta(issuer_id)
        if meta is not None:
            meta["epoch"] = view.epoch
            meta["staleness_s"] = round(view.age_s(), 6)
        return meta

    def stats(self) -> dict:
        body = {
            "queue_lanes": self.batcher.queue_lanes(),
            "queue_cap": self.batcher.max_queue_lanes,
            "max_batch": self.batcher.max_batch,
            "max_delay_s": self.batcher.max_delay_s,
        }
        body.update(self.snapshots.stats())
        if self.cache is not None:
            body.update(self.cache.stats())
        body["filter_first"] = bool(self.filter_first)
        if self.filter_tier is not None:
            body["filter_epoch"] = self.filter_tier.epoch
            body["filter_staleness_s"] = round(self.filter_tier.age_s(), 6)
            body["filter_serials"] = self.filter_tier.artifact.n_serials
            body["filter_format"] = self.filter_tier.artifact.fmt
            body["filter_groups_reused"] = self.filter_build_cache.hits
        if self.distributor is not None:
            body.update(self.distributor.stats())
        return body

    def close(self) -> None:
        self.batcher.close()


def _parse_query(q: dict, oracle: MembershipOracle):
    """One JSON query object → (issuer_idx, exp_hour, serial_bytes).

    Unknown issuers map to idx -1: the lookup treats them as
    device-ineligible and the host-set probe can't match either, so
    the answer is an honest ``known: false`` (the table has, by
    definition, never counted a serial for an issuer the registry has
    never seen)."""
    issuer = q.get("issuer")
    exp = q.get("expDate")
    serial_hex = q.get("serial")
    if not isinstance(issuer, str) or not isinstance(exp, str) \
            or not isinstance(serial_hex, str):
        raise ValueError("query needs string issuer, expDate, serial")
    try:
        serial = bytes.fromhex(serial_hex)
    except ValueError as err:
        raise ValueError(f"serial is not hex: {err}") from None
    try:
        eh = ExpDate.parse(exp).unix_hour()
    except ValueError as err:
        raise ValueError(f"bad expDate {exp!r}: {err}") from None
    return (oracle.resolve_issuer(issuer), eh, serial)


def _ready(code: int, body: dict):
    """A ``finish`` whose answer is known already."""
    return lambda: (code, body)


class QueryServer:
    """Background HTTP server for the query plane (``queryPort``): the
    oracle, the routes' handlers, and the one thread that talks to the
    clients (:class:`_Front`). Port 0 binds ephemeral (tests),
    ``stop()`` shuts down cleanly. ``transport`` overrides the CT-log
    HTTP transport for the ``/getcert`` proxy (tests route it at an
    in-process fake log)."""

    # The deployments' request deadline: a connection on which no byte
    # moves for this long is dropped.
    idle_s = 10.0

    def __init__(self, agg, port: int, host: str = "0.0.0.0",
                 max_batch: int = 4096, max_delay_s: float = 0.002,
                 max_queue_lanes: int = 1 << 16,
                 max_staleness_s: float = 1.0,
                 device: Optional[bool] = None, replicas: int = 0,
                 cache_size: int = 0, transport=None,
                 filter_first: Optional[bool] = None,
                 filter_fp_rate: float = 0.0,
                 distrib_history: int = 0,
                 max_delta_chain: int = 0) -> None:
        self.host = host
        self.port = int(port)
        self.oracle = MembershipOracle(
            agg, max_batch=max_batch, max_delay_s=max_delay_s,
            max_queue_lanes=max_queue_lanes,
            max_staleness_s=max_staleness_s, device=device,
            replicas=replicas, cache_size=cache_size,
            filter_first=filter_first, filter_fp_rate=filter_fp_rate,
            distrib_history=distrib_history,
            max_delta_chain=max_delta_chain)
        self._transport = transport
        # Optional SLO probe (round 23): a callable returning the
        # current breach-reason list; non-empty flips /healthz to 503.
        self.slo_check = None
        self._front: Optional[_Front] = None

    # -- request handling ------------------------------------------------
    def begin_query(self, body: dict, answered):
        """A ``/query`` in two halves, so that the front's loop waits in
        neither: parse and admit now, ``finish() -> (code, body)``
        later. Returns ``(finish, lanes)``. With ``lanes`` > 0 the
        request is in the batcher's queue: ``answered()`` will be
        called once, on the batcher's thread, and ``finish`` only after
        it. With 0, ``finish`` may be called at once: a 400, a 429, or
        an answer the cache or the filter tier had whole."""
        queries = body.get("queries")
        single = queries is None
        if single:
            queries = [body]
        if not isinstance(queries, list) or not queries:
            return _ready(400, {"error": "queries must be a non-empty list"}), 0
        try:
            items = [_parse_query(q, self.oracle) for q in queries]
        except (ValueError, AttributeError, TypeError) as err:
            return _ready(400, {"error": str(err)}), 0
        timeout_ms = body.get("timeoutMs")
        timeout_s = float(timeout_ms) / 1e3 if timeout_ms else None
        try:
            asked = self.oracle.admit(items, timeout_s, answered)
        except Overloaded as err:
            return _ready(429, {"error": "overloaded", "detail": str(err)}), 0
        return partial(self._finish_query, asked, single), asked.lanes

    def _finish_query(self, asked: Admitted, single: bool) -> tuple[int, dict]:
        try:
            results = self.oracle.query_raw(asked)
        except DeadlineExceeded as err:
            return 504, {"error": "deadline_exceeded", "detail": str(err)}
        # A result row comes from one pinned view, but rows can span
        # views (cache hits at older epochs; oversized bulks split into
        # sub-batches) — report the OLDEST epoch consulted and the
        # LARGEST staleness, so the surfaced bound errs conservative.
        epoch = min(r[1] for r in results)
        staleness = max(r[2] for r in results)
        out = {
            "results": [{"known": known} for known, _, _ in results],
            "epoch": epoch,
            "staleness_s": round(staleness, 6),
        }
        if single:
            out["known"] = out["results"][0]["known"]
        return 200, out

    def handle_get(self, path: str, qs: str, headers) -> tuple:
        """The routes that can block or stream (the front runs them on
        its pool): ``(code, body[, extra headers])``."""
        from urllib.parse import parse_qsl, unquote

        if path == "/healthz":
            return self.handle_healthz()
        if path.startswith("/issuer/"):
            return self.handle_issuer(unquote(path[len("/issuer/"):]))
        if path == "/getcert":
            return self.handle_getcert(dict(parse_qsl(qs)))
        return self.handle_filter(
            unquote(path[len("/filter"):]).lstrip("/"), req_headers=headers)

    def handle_issuer(self, issuer_id: str) -> tuple[int, dict]:
        meta = self.oracle.issuer_meta(issuer_id)
        if meta is None:
            return 404, {"error": "unknown issuer", "issuer": issuer_id}
        return 200, meta

    # Cache policies per distribution resource: "latest"-shaped
    # resources move every epoch, epoch-pinned resources never change.
    _CC_LATEST = "public, max-age=60, must-revalidate"
    _CC_IMMUTABLE = "public, max-age=31536000, immutable"

    def _blob_response(self, blob: bytes, etag: str, req_headers,
                       cache_control: str, cache_key=None,
                       created_wall: Optional[float] = None,
                       epoch: Optional[int] = None):
        """One distribution payload: strong-ETag conditional GET
        (If-None-Match ⇒ 304 with zero body bytes), Accept-Encoding
        negotiation against the pre-compressed cache, and per-artifact
        cache headers. ``req_headers``: the request's, by lower-cased
        name, as the front parses them."""
        from email.utils import formatdate

        headers = {"ETag": etag, "Cache-Control": cache_control,
                   "Vary": "Accept-Encoding"}
        if created_wall is not None:
            headers["Last-Modified"] = formatdate(created_wall,
                                                  usegmt=True)
        if epoch is not None:
            headers["X-Filter-Epoch"] = str(epoch)
        inm = (req_headers.get("if-none-match", "")
               if req_headers else "")
        if inm and (inm.strip() == "*"
                    or etag in [t.strip() for t in inm.split(",")]):
            incr_counter("distrib", "http_304")
            return 304, b"", headers
        distributor = self.oracle.distributor
        if req_headers is not None and distributor is not None:
            from ct_mapreduce_tpu.distrib import negotiate_encoding

            enc = negotiate_encoding(
                req_headers.get("accept-encoding", ""))
            if enc:
                payload = distributor.encoded(cache_key, blob, enc)
                headers["Content-Encoding"] = enc
                incr_counter("distrib", "bytes_sent",
                             value=float(len(payload)))
                return 200, payload, headers
        incr_counter("distrib", "bytes_sent", value=float(len(blob)))
        return 200, blob, headers

    def handle_filter(self, rest: str, req_headers=None):
        """The distribution surface (docs/FILTER_FORMAT.md formats):

        - ``GET /filter`` — the latest full ``CTMRFL01`` artifact;
        - ``GET /filter/manifest`` — the chain manifest JSON (latest
          epoch + hash, delta links with per-link SHA-256, anchors);
        - ``GET /filter/container/<kind>`` — the latest artifact in an
          upstream container encoding (``mlbf`` | ``clubcard``);
        - ``GET /filter/delta/<from>/<to>`` — the concatenated
          ``CTMRDL01`` links replaying epoch *from* to *to* (404 ⇒
          no contiguous chain: full-pull);
        - ``GET /filter/<issuer>/<expDate>`` — a standalone
          single-group artifact slice.

        Every binary answer carries a strong ETag (SHA-256 of the
        deterministic bytes — identical on every worker of a fleet),
        honors ``If-None-Match`` with 304, negotiates
        gzip/zstd via ``Accept-Encoding``, and sets per-artifact
        ``Cache-Control``/``Last-Modified``. 404 when the tier is cold
        or the resource is unknown."""
        tier = self.oracle.filter_tier
        distributor = self.oracle.distributor
        latest = distributor.latest() if distributor is not None else None
        if tier is None and latest is None:
            return 404, {"error": "filter tier not armed "
                                  "(emitFilter / refresh_filter)"}
        parts = [p for p in rest.split("/") if p] if rest else []
        if not parts:
            incr_counter("distrib", "http_full")
            if latest is not None:
                return self._blob_response(
                    latest.blob, latest.etag, req_headers,
                    self._CC_LATEST, cache_key=("full", latest.epoch),
                    created_wall=latest.created_wall,
                    epoch=latest.epoch)
            blob = tier.artifact.to_bytes()
            from ct_mapreduce_tpu.distrib import publish as _pub

            return self._blob_response(blob, _pub.etag_of(blob),
                                       req_headers, self._CC_LATEST,
                                       epoch=tier.epoch)
        if parts[0] == "manifest":
            if distributor is None:
                return 404, {"error": "distribution store not armed"}
            incr_counter("distrib", "http_manifest")
            return 200, distributor.manifest()
        if parts[0] == "container":
            if latest is None:
                return 404, {"error": "no published artifact"}
            if len(parts) != 2 or parts[1] not in latest.containers:
                return 404, {"error": "unknown container kind",
                             "kinds": sorted(latest.containers)}
            incr_counter("distrib", "http_container")
            return self._blob_response(
                latest.containers[parts[1]],
                latest.container_etags[parts[1]], req_headers,
                self._CC_LATEST,
                cache_key=("container", latest.epoch, parts[1]),
                created_wall=latest.created_wall, epoch=latest.epoch)
        if parts[0] == "delta":
            if distributor is None:
                return 404, {"error": "distribution store not armed"}
            if len(parts) != 3:
                return 400, {"error": "use /filter/delta/<from>/<to>"}
            try:
                from_e, to_e = int(parts[1]), int(parts[2])
            except ValueError:
                return 400, {"error": "delta epochs must be integers"}
            bundle = distributor.delta_bundle(from_e, to_e)
            if bundle is None:
                return 404, {"error": "no delta chain",
                             "fromEpoch": from_e, "toEpoch": to_e,
                             "hint": "full-pull /filter"}
            incr_counter("distrib", "http_delta")
            from ct_mapreduce_tpu.distrib import publish as _pub

            return self._blob_response(
                bundle, _pub.etag_of(bundle), req_headers,
                self._CC_IMMUTABLE, cache_key=("delta", from_e, to_e),
                epoch=to_e)
        if len(parts) != 2:
            return 400, {"error": "use /filter/<issuer>/<expDate>"}
        art = (tier.artifact if tier is not None
               else None)
        if art is None:
            from ct_mapreduce_tpu.filter import FilterArtifact

            art = FilterArtifact.from_bytes(latest.blob)
        blob = art.group_bytes(parts[0], parts[1])
        if blob is None:
            return 404, {"error": "no filter group",
                         "issuer": parts[0], "expDate": parts[1]}
        from ct_mapreduce_tpu.distrib import publish as _pub

        return self._blob_response(blob, _pub.etag_of(blob),
                                   req_headers, self._CC_LATEST)

    def handle_healthz(self) -> tuple[int, dict]:
        from ct_mapreduce_tpu.telemetry.metrics import get_sink

        counters = get_sink().snapshot().get("counters", {})
        # SLO hook (round 23): ct-fetch attaches its rule evaluation;
        # any breach renders the same JSON body under HTTP 503 so load
        # balancers act on the code while operators read the reasons.
        degraded: list = []
        if self.slo_check is not None:
            try:
                degraded = list(self.slo_check())
            except Exception as err:  # the probe must answer, not 500
                degraded = [f"slo check failed: "
                            f"{type(err).__name__}: {err}"]
        body = {
            "healthy": not degraded,
            **self.oracle.stats(),
            "shed_total": counters.get("serve.shed", 0.0),
            "batches_total": counters.get("serve.batches", 0.0),
            "cache_hit_total": counters.get("serve.cache_hit", 0.0),
            "cache_miss_total": counters.get("serve.cache_miss", 0.0),
            "device_fallback_total": counters.get(
                "serve.device_fallback", 0.0),
        }
        if degraded:
            body["degraded"] = degraded
        return (503 if degraded else 200), body

    def handle_getcert(self, params: dict) -> tuple[int, dict]:
        log_url = params.get("log")
        index = params.get("index")
        if not log_url or index is None:
            return 400, {"error": "log and index are required"}
        try:
            index = int(index)
        except ValueError:
            return 400, {"error": f"index is not an integer: {index!r}"}
        from ct_mapreduce_tpu.core.der import der_to_pem
        from ct_mapreduce_tpu.ingest.ctclient import CTLogClient
        from ct_mapreduce_tpu.ingest.leaf import (
            LeafDecodeError,
            decode_json_entry,
        )

        try:
            client = CTLogClient(log_url, transport=self._transport)
            entries = client.get_raw_entries(index, index)
        except Exception as err:
            return 502, {"error": f"log fetch failed: {err}"}
        pems = []
        for raw in entries:
            try:
                entry = decode_json_entry(
                    raw.index,
                    {"leaf_input": raw.leaf_input,
                     "extra_data": raw.extra_data},
                )
            except LeafDecodeError as err:
                return 502, {"error": f"undecodable entry: {err}"}
            pem = der_to_pem(entry.cert_der)
            pems.append(pem.decode() if isinstance(pem, bytes) else pem)
        if not pems:
            return 404, {"error": f"no entry at index {index}"}
        return 200, {"log": log_url, "index": index, "pem": "".join(pems)}

    # -- server lifecycle ------------------------------------------------
    def start(self) -> "QueryServer":
        self._front = _Front(self, self.host, self.port)
        self.port = self._front.port  # resolve port 0
        self._front.start()
        return self

    def stop(self) -> None:
        if self._front is not None:
            self._front.stop()
            self._front = None
        self.oracle.close()


class _Conn:
    """One accepted connection, between the loop's turns."""

    __slots__ = ("sock", "inbuf", "out", "watching", "keep", "active",
                 "wait", "requests", "bytes_in", "bytes_out", "tracer",
                 "span_id", "t0_ns", "tts_ns", "cpu_ns")

    def __init__(self, sock: socket.socket, now: float) -> None:
        self.sock: Optional[socket.socket] = sock
        self.inbuf = bytearray()
        self.out = b""          # what of the answer is not sent yet
        self.watching = 0       # the selector events it is registered for
        self.keep = False       # the client asked to keep the connection
        self.active = now       # the last byte moved (time.monotonic)
        self.wait = None        # a /query in the batcher's queue
        self.requests = self.bytes_in = self.bytes_out = 0
        self.tracer = None      # front.conn: the tracer it began under


class _BadRequest(Exception):
    """A request the front refuses before any handler: (code, why)."""


class _Front:
    """The query plane's front: ONE thread (``query-front``) and a
    ``selectors`` loop over the listening socket and every connection.
    It accepts, reads until a request is whole, parses the request line
    and the headers from the bytes, answers and closes; no connection
    has a thread and none goes through ``http.server``. A ``POST
    /query`` is admitted to the batcher with a callback and the loop
    moves on: the batcher's thread puts the connection on ``_done`` and
    wakes the loop (a byte down a socketpair, one for all the answers of
    a batch), which writes the answer. The routes that can block or
    stream (``/filter...``, ``/getcert``, ``/issuer/``, ``/healthz``)
    leave the loop for a pool of ``POOL_THREADS``, which runs the
    handler and writes on the connection's blocking socket, then hands
    the connection back; ``front.pool_requests`` counts them.

    Sockets stay blocking and every ``recv`` / ``send`` of the loop
    passes ``MSG_DONTWAIT``: a connection that has its request behind
    its ``connect`` (the common case) costs accept, recv, send, close
    and never meets the selector. The protocol is the old front's: the
    answer closes the connection (``HTTP/1.0``) unless the client sent
    ``Connection: keep-alive`` (then ``HTTP/1.1``, and requests may be
    pipelined). A header block over ``MAX_HEAD`` or a body over
    ``MAX_BODY`` is refused and the connection closed; one on which no
    byte moves for ``idle_s`` is dropped."""

    # The standard library listens with a backlog of 5. Independent
    # clients arrive in bursts (after any pause of this process or its
    # machine, everything that was due meanwhile arrives at once), and
    # connections beyond the backlog are dropped in the kernel, where
    # no 429 says so: they come back in step, a second, three and seven
    # seconds later, and collide again. The admission queue sheds load
    # (``Overloaded``); the socket must not.
    BACKLOG = 1024
    MAX_HEAD = 64 << 10
    MAX_BODY = 16 << 20
    POOL_THREADS = 4
    SWEEP_S = 1.0        # how often idle connections are looked for

    def __init__(self, server: QueryServer, host: str, port: int) -> None:
        self._server = server
        self.idle_s = float(server.idle_s)
        self._listener = socket.create_server((host, port),
                                              backlog=self.BACKLOG)
        self._listener.setblocking(False)
        self.port = self._listener.getsockname()[1]
        self._sel = selectors.DefaultSelector()
        self._sel.register(self._listener, selectors.EVENT_READ, None)
        # The batcher's thread and the pool's hand connections back
        # here; the flag keeps it to one wake-up byte a drain.
        self._wake_r, self._wake_w = socket.socketpair()
        self._wake_r.setblocking(False)
        self._wake_w.setblocking(False)
        self._sel.register(self._wake_r, selectors.EVENT_READ, self)
        self._done: deque = deque()
        self._woken = False
        self._live: set[_Conn] = set()
        self._stopping = False
        self._pool = ThreadPoolExecutor(self.POOL_THREADS,
                                        thread_name_prefix="query-pool")
        self._thread = threading.Thread(target=self._run, name="query-front",
                                        daemon=True)

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        """Nobody still connected is answered: the loop ends, a pool
        thread in a write is freed by its socket's shutdown."""
        self._stopping = True
        self._post(None)
        self._thread.join()
        for conn in list(self._live):
            try:
                conn.sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        self._pool.shutdown(wait=True, cancel_futures=True)
        for conn in list(self._live):
            self._close(conn)
        self._sel.close()
        for sock in (self._listener, self._wake_r, self._wake_w):
            sock.close()

    # -- the loop --------------------------------------------------------
    def _run(self) -> None:
        swept = time.monotonic()
        cpu = 0
        while not self._stopping:
            watched = len(self._sel.get_map()) > 2
            ready = self._sel.select(self.SWEEP_S if watched else None)
            tracer = trace.get_tracer()
            # The CPU clock is read once a turn, at its end, and only
            # under a tracer (a read is a syscall on the chip's host): a
            # turn runs from the end of the one before it, the loop's
            # own wait for the event included, so the connections'
            # shares add up to all the CPU this thread used.
            if tracer is None:
                cpu = 0
            elif not cpu:
                cpu = time.thread_time_ns()
            for key, _ in ready:
                if key.data is None:
                    cpu = self._accept(tracer, cpu)
                elif key.data is self:
                    cpu = self._drain(tracer, cpu)
                else:
                    cpu = self._turn(key.data, self._ready, tracer, cpu)
            now = time.monotonic()
            if watched and now - swept >= self.SWEEP_S:
                swept = now
                for conn in [k.data for k in self._sel.get_map().values()
                             if isinstance(k.data, _Conn)
                             and now - k.data.active > self.idle_s]:
                    cpu = self._turn(conn, self._close, tracer, cpu)

    def _turn(self, conn: _Conn, step, tracer, cpu: int) -> int:
        """One turn of the loop for one connection: ``step(conn)``, then
        the thread's CPU since ``cpu`` (the end of the turn before)
        added to the connection's and, if the turn closed it, its
        ``front.conn`` recorded. Returns the CPU clock for the next
        turn. A turn that raises costs its connection, never the
        loop."""
        try:
            step(conn)
        except Exception:
            self._close(conn)
        if tracer is None:
            return cpu
        now = time.thread_time_ns()
        if conn.tracer is tracer:
            conn.cpu_ns += now - cpu
            if conn.sock is None:
                conn.tracer = None
                tracer.record_span(
                    "front.conn", "front", conn.t0_ns,
                    time.perf_counter_ns(), tts_ns=conn.tts_ns,
                    tdur_ns=conn.cpu_ns, span_id=conn.span_id,
                    requests=conn.requests, bytes_in=conn.bytes_in,
                    bytes_out=conn.bytes_out)
        return now

    def _accept(self, tracer, cpu: int) -> int:
        """One connection off the backlog (the selector says so again
        while there are more) and its first turn: the request is
        usually right behind the ``connect``."""
        t0_ns = time.perf_counter_ns() if tracer is not None else 0
        try:
            sock, _ = self._listener.accept()
        except OSError as err:
            if err.errno in (errno.EMFILE, errno.ENFILE, errno.ENOBUFS,
                             errno.ENOMEM):
                time.sleep(0.05)  # no descriptor to accept into: not a spin
            return cpu
        conn = _Conn(sock, time.monotonic())
        self._live.add(conn)
        if tracer is not None:
            conn.tracer, conn.span_id = tracer, tracer.next_id()
            conn.t0_ns, conn.tts_ns, conn.cpu_ns = t0_ns, cpu, 0
        return self._turn(conn, self._read, tracer, cpu)

    def _post(self, conn: Optional[_Conn]) -> None:
        """From any thread: ``conn`` has its answer (the batcher's
        thread) or was answered (the pool's); the loop takes it from
        here. The flag is cleared before a drain begins, so a post that
        finds it set is drained by the wake-up that set it."""
        self._done.append(conn)
        if not self._woken:
            self._woken = True
            try:
                self._wake_w.send(b"\0")
            except OSError:
                pass  # full of wake-ups already, or stopped

    def _drain(self, tracer, cpu: int) -> int:
        try:
            self._wake_r.recv(4096)
        except OSError:
            pass
        self._woken = False
        while self._done:
            conn = self._done.popleft()
            if conn is not None and conn.sock is not None:
                cpu = self._turn(conn, self._handed_back, tracer, cpu)
        return cpu

    # -- a connection's turns ---------------------------------------------
    def _watch(self, conn: _Conn, events: int) -> None:
        if conn.watching == 0:
            self._sel.register(conn.sock, events, conn)
        elif conn.watching != events:
            self._sel.modify(conn.sock, events, conn)
        conn.watching = events

    def _unwatch(self, conn: _Conn) -> None:
        if conn.watching:
            self._sel.unregister(conn.sock)
            conn.watching = 0

    def _close(self, conn: _Conn) -> None:
        if conn.sock is not None:
            self._unwatch(conn)
            conn.sock.close()
            conn.sock = None
            self._live.discard(conn)

    def _ready(self, conn: _Conn) -> None:
        if conn.watching == selectors.EVENT_WRITE:
            self._write(conn)
        else:
            self._read(conn)

    def _read(self, conn: _Conn) -> None:
        try:
            data = conn.sock.recv(1 << 16, socket.MSG_DONTWAIT)
        except (BlockingIOError, InterruptedError):
            self._watch(conn, selectors.EVENT_READ)
            return
        except OSError:
            data = b""
        if not data:  # the client is gone
            self._close(conn)
            return
        conn.active = time.monotonic()
        conn.inbuf += data
        self._advance(conn)

    def _advance(self, conn: _Conn) -> None:
        """Serve what ``inbuf`` holds: a whole request is dispatched,
        and the connection reads no further until it is answered; a
        partial one waits for its bytes."""
        try:
            request = self._parse(conn.inbuf)
        except _BadRequest as bad:
            conn.keep = False
            self._unwatch(conn)
            self._respond(conn, bad.args[0], {"error": bad.args[1]})
            return
        if request is None:
            self._watch(conn, selectors.EVENT_READ)
            return
        self._unwatch(conn)
        self._dispatch(conn, *request)

    def _parse(self, buf: bytearray):
        """``(method, target, headers, body)`` off the front of ``buf``
        once the header block and ``Content-Length`` bytes are in, else
        None; :class:`_BadRequest` for what no handler should see."""
        end = buf.find(b"\r\n\r\n")
        if end < 0 or end > self.MAX_HEAD:
            if len(buf) > self.MAX_HEAD:
                raise _BadRequest(431, "bad request: header block over "
                                       f"{self.MAX_HEAD} bytes")
            return None
        lines = buf[:end].decode("latin-1").split("\r\n")
        try:
            method, target, _version = lines[0].split()
        except ValueError:
            raise _BadRequest(
                400, f"bad request: request line {lines[0]!r}") from None
        headers: dict[str, str] = {}
        for line in lines[1:]:
            name, colon, value = line.partition(":")
            if colon:
                headers[name.strip().lower()] = value.strip()
        try:
            length = int(headers.get("content-length", "0"))
            if length < 0:
                raise ValueError
        except ValueError:
            raise _BadRequest(400, "bad request: Content-Length "
                              f"{headers['content-length']!r}") from None
        if length > self.MAX_BODY:
            raise _BadRequest(413, "bad request: body over "
                                   f"{self.MAX_BODY} bytes")
        if len(buf) < end + 4 + length:
            return None
        body = bytes(buf[end + 4: end + 4 + length])
        del buf[: end + 4 + length]
        return method, target, headers, body

    def _dispatch(self, conn: _Conn, method: str, target: str,
                  headers: dict, body: bytes) -> None:
        conn.requests += 1
        conn.bytes_in += len(body)
        conn.keep = "keep-alive" in headers.get("connection", "").lower()
        path, _, qs = target.partition("?")
        path = path.rstrip("/") or "/"
        pooled = method == "GET" and (
            path in ("/healthz", "/getcert", "/filter")
            or path.startswith(("/issuer/", "/filter/")))
        incr_counter("front", "requests")
        incr_counter("front", "pool_requests", value=float(pooled))
        if pooled:
            self._pool.submit(self._pooled, conn, path, qs, headers)
        elif method == "POST" and path == "/query":
            self._query(conn, headers, body)
        elif method in ("GET", "POST"):
            self._respond(conn, 404, {"error": "not found"})
        else:
            self._respond(conn, 501, {
                "error": f"unsupported method {method!r}"})

    def _query(self, conn: _Conn, headers: dict, body: bytes) -> None:
        try:
            doc = json.loads(body or b"{}")
            if not isinstance(doc, dict):
                raise ValueError("body must be a JSON object")
        except ValueError as err:  # JSONDecodeError, UnicodeDecodeError
            self._respond(conn, 400, {"error": f"bad request: {err}"})
            return
        # Cross-process correlation (round 23): the client's traceparent
        # goes into the batcher's queue with the request, and onto the
        # serve.wait this connection records.
        ids = trace.parse_traceparent(
            headers.get(trace.TRACEPARENT_HEADER)) or (None,)
        try:
            with trace.trace_context(*ids):
                finish, lanes = self._server.begin_query(
                    doc, partial(self._post, conn))
            if lanes:
                conn.wait = (finish, lanes, time.perf_counter_ns(), ids)
            else:
                self._respond(conn, *finish())
        except Exception as err:  # the server must answer
            self._respond(conn, 500,
                          {"error": f"{type(err).__name__}: {err}"})

    def _handed_back(self, conn: _Conn) -> None:
        """``conn`` is off ``_done``: the batcher has its answer, or the
        pool wrote it."""
        if conn.wait is None:
            self._answered(conn)
            return
        finish, lanes, t0_ns, ids = conn.wait
        conn.wait = None
        t1_ns = time.perf_counter_ns()
        add_sample("serve", "wait_s", value=(t1_ns - t0_ns) / 1e9)
        if conn.tracer is not None:
            with trace.trace_context(*ids):
                conn.tracer.record_span("serve.wait", "serve", t0_ns, t1_ns,
                                        parent=conn.span_id, lanes=lanes)
        try:
            answer = finish()
        except Exception as err:  # the server must answer
            answer = 500, {"error": f"{type(err).__name__}: {err}"}
        self._respond(conn, *answer)

    def _encode(self, conn: _Conn, code: int, body,
                headers=None) -> tuple[bytes, bytes]:
        """The answer's head and payload; what it carries is counted."""
        if isinstance(body, (bytes, bytearray)):
            payload, ctype = bytes(body), "application/octet-stream"
        else:
            payload, ctype = json.dumps(body).encode(), "application/json"
        lines = [f"HTTP/1.{int(conn.keep)} {code} {HTTPStatus(code).phrase}",
                 f"Content-Type: {ctype}",
                 f"Content-Length: {len(payload)}"]
        lines += [f"{name}: {value}"
                  for name, value in sorted((headers or {}).items())]
        if conn.keep:
            lines.append("Connection: keep-alive")
        conn.bytes_out += len(payload)
        if code >= 400:
            incr_counter("serve", "http_errors")
        return ("\r\n".join(lines) + "\r\n\r\n").encode("latin-1"), payload

    def _respond(self, conn: _Conn, code: int, body, headers=None) -> None:
        head, payload = self._encode(conn, code, body, headers)
        conn.out = head + payload
        self._write(conn)

    def _write(self, conn: _Conn) -> None:
        try:
            sent = conn.sock.send(conn.out, socket.MSG_DONTWAIT)
        except (BlockingIOError, InterruptedError):
            sent = 0
        except OSError:
            self._close(conn)
            return
        if sent < len(conn.out):
            if sent:
                conn.active = time.monotonic()
                conn.out = conn.out[sent:]
            self._watch(conn, selectors.EVENT_WRITE)
            return
        conn.out = b""
        self._answered(conn)

    def _answered(self, conn: _Conn) -> None:
        """The answer is out: the next request of a kept connection
        (it may be in ``inbuf`` already), or the close."""
        if conn.keep:
            conn.active = time.monotonic()
            self._advance(conn)
        else:
            self._close(conn)

    # -- the pool --------------------------------------------------------
    def _pooled(self, conn: _Conn, path: str, qs: str,
                headers: dict) -> None:
        """On a pool thread: a route that can block (``/getcert`` asks a
        log) or stream (a filter is up to 100 MB), written on the
        connection's own blocking socket, at most 1 MB a ``send`` so
        that the socket layer never holds a second copy; a client that
        takes no byte for ``idle_s`` is dropped. Its CPU is the
        connection's."""
        cpu = time.thread_time_ns() if conn.tracer is not None else 0
        ids = trace.parse_traceparent(
            headers.get(trace.TRACEPARENT_HEADER)) or (None,)
        try:
            with trace.trace_context(*ids):
                answer = self._server.handle_get(path, qs, headers)
        except Exception as err:  # the server must answer
            answer = 500, {"error": f"{type(err).__name__}: {err}"}
        head, payload = self._encode(conn, *answer)
        view = memoryview(payload)
        try:
            conn.sock.settimeout(self.idle_s)
            conn.sock.sendall(head)
            off = 0
            while off < len(view):
                off += conn.sock.send(view[off: off + (1 << 20)])
            conn.sock.settimeout(None)
        except OSError:
            conn.keep = False
        if conn.tracer is not None:
            conn.cpu_ns += time.thread_time_ns() - cpu
        self._post(conn)
