"""Query plane (serve/): dynamic batching, snapshot isolation, and the
HTTP membership API.

The load-bearing test is the threaded ingest+query stress
(``test_concurrent_ingest_query_consistency``): queries issued WHILE
the table is growing and batches are folding must return
snapshot-consistent answers — every serial acked longer than the
staleness bound before the query reads as known, and a serial never
fed can never read known (ISSUE 5 acceptance)."""

import contextlib
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
from ct_mapreduce_tpu.core import der as hostder
from ct_mapreduce_tpu.core.types import ExpDate, Issuer
from ct_mapreduce_tpu.serve.batcher import (
    DeadlineExceeded,
    MicroBatcher,
    Overloaded,
)
from ct_mapreduce_tpu.serve.cache import HotSerialCache
from ct_mapreduce_tpu.serve.server import (
    MembershipOracle,
    QueryServer,
    resolve_serve,
)
from ct_mapreduce_tpu.serve.snapshot import (
    ReplicaPool,
    capture_view,
)
from ct_mapreduce_tpu.utils import syncerts


@pytest.fixture(scope="module")
def template():
    return syncerts.make_template(issuer_cn="Serve Test CA")


def _serial_bytes(tpl, j: int) -> bytes:
    der = syncerts.stamp_serial(tpl, j)
    return der[tpl.serial_off : tpl.serial_off + tpl.serial_len]


def _identity(tpl):
    """(issuer_id, exp_hour) shared by every restamp of a template."""
    eh = hostder.parse_cert(tpl.leaf_der).not_after_unix_hour
    issuer_id = Issuer.from_spki(
        hostder.parse_cert(tpl.issuer_der).spki).id()
    return issuer_id, eh


# -- MicroBatcher ---------------------------------------------------------


def test_batcher_coalesces_concurrent_requests():
    """Concurrent single-item submits form batches > 1 (the whole
    point of the micro-batcher): a slow oracle keeps the worker busy
    while followers queue, so the next batch carries them all."""
    sizes = []

    def oracle(items):
        sizes.append(len(items))
        time.sleep(0.02)
        return [it * 2 for it in items]

    b = MicroBatcher(oracle, max_batch=64, max_delay_s=0.005)
    try:
        results = {}

        def client(k):
            results[k] = b.submit([k])[0]

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert results == {k: 2 * k for k in range(24)}
        assert sum(sizes) == 24
        assert max(sizes) > 1, f"no coalescing happened: {sizes}"
    finally:
        b.close()


def test_batcher_respects_max_batch():
    sizes = []

    def oracle(items):
        sizes.append(len(items))
        return items

    b = MicroBatcher(oracle, max_batch=4, max_delay_s=0.05)
    try:
        # One request never splits; several small ones pack up to the cap.
        outs = []
        threads = [threading.Thread(
            target=lambda k=k: outs.append(tuple(b.submit([k, k])))
        ) for k in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert sorted(outs) == sorted((k, k) for k in range(6))
        assert max(sizes) <= 4
    finally:
        b.close()


def test_batcher_sheds_on_full_queue_with_explicit_rejection():
    release = threading.Event()

    def oracle(items):
        release.wait(timeout=5)
        return items

    b = MicroBatcher(oracle, max_batch=8, max_delay_s=0.001,
                     max_queue_lanes=4)
    try:
        accepted, shed = [], []

        def client(k):
            try:
                accepted.append(b.submit([k])[0])
            except Overloaded:
                shed.append(k)

        # First submit occupies the worker; the queue then fills to its
        # 4-lane cap and the rest must be REJECTED, not queued.
        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(12)]
        for t in threads:
            t.start()
            time.sleep(0.002)  # deterministic arrival order
        release.set()
        for t in threads:
            t.join()
        assert shed, "no request was shed despite a 4-lane cap"
        assert accepted, "every request was shed"
        assert len(accepted) + len(shed) == 12
        assert sorted(accepted + shed) == list(range(12))
    finally:
        release.set()
        b.close()


def test_batcher_deadline_expires_queued_request():
    release = threading.Event()

    def oracle(items):
        release.wait(timeout=5)
        return items

    b = MicroBatcher(oracle, max_batch=8, max_delay_s=0.001)
    try:
        first = threading.Thread(target=lambda: b.submit([0]))
        first.start()
        time.sleep(0.02)  # worker is now blocked inside the oracle
        # Unblock the oracle AFTER the second request's 10 ms deadline
        # has passed — by the time its batch forms, it is stale.
        threading.Timer(0.1, release.set).start()
        with pytest.raises(DeadlineExceeded):
            b.submit([1], timeout_s=0.01)
        first.join(timeout=5)
    finally:
        release.set()
        b.close()


def test_batcher_close_fails_pending_loudly():
    hold = threading.Event()

    def oracle(items):
        hold.wait(timeout=5)
        return items

    b = MicroBatcher(oracle, max_batch=2, max_delay_s=0.001)
    errs = []

    def client():
        try:
            b.submit([1])
        except RuntimeError as e:
            errs.append(e)

    t = threading.Thread(target=client)
    t.start()
    time.sleep(0.02)
    hold.set()
    b.close()
    t.join(timeout=5)
    with pytest.raises(RuntimeError):
        b.submit([2])


# -- snapshot views -------------------------------------------------------


def test_view_membership_and_staleness(template):
    agg = TpuAggregator(capacity=1 << 12, batch_size=64)
    entries = [(syncerts.stamp_serial(template, j), template.issuer_der)
               for j in range(40)]
    agg.ingest(entries)
    issuer_id, eh = _identity(template)
    idx = agg.registry.index_of_issuer_id(issuer_id)

    view = capture_view(agg, epoch=1)
    present = [(idx, eh, _serial_bytes(template, j)) for j in range(40)]
    absent = [(idx, eh, _serial_bytes(template, j))
              for j in range(1000, 1010)]
    got = view.lookup(present + absent)
    assert got[:40].all()
    assert not got[40:].any()
    assert view.age_s() >= 0
    # Unknown issuer / out-of-range lanes answer False, never crash.
    odd = [(-1, eh, b"\x01"), (idx, 0, b"\x01"),
           (idx, eh, b"\x01" * 64)]
    assert not view.lookup(odd).any()
    # The view is PINNED: later ingest must not leak in.
    agg.ingest([(syncerts.stamp_serial(template, 500),
                 template.issuer_der)])
    assert not view.lookup([(idx, eh, _serial_bytes(template, 500))])[0]
    assert capture_view(agg, epoch=2).lookup(
        [(idx, eh, _serial_bytes(template, 500))])[0]


def test_view_covers_host_lane_serials(template):
    """Serials that took the exact host lane (oversized DER) are part
    of membership too — the view freezes the host sets."""
    agg = TpuAggregator(capacity=1 << 12, batch_size=64)
    issuer_idx = agg.registry.get_or_assign(template.issuer_der)
    entries = [(syncerts.stamp_serial(template, j), template.issuer_der)
               for j in range(4)]
    agg.ingest(entries)
    # Land one serial in the exact host lane through the same dedup
    # call every flagged lane takes.
    fields = hostder.parse_cert(syncerts.stamp_serial(template, 99))
    agg._host_dedup(fields, issuer_idx, fields.not_after_unix_hour)
    view = capture_view(agg, epoch=1)
    _, eh = _identity(template)
    assert view.lookup(
        [(issuer_idx, eh, _serial_bytes(template, 99))])[0]


def test_view_device_mode_parity(template):
    """device=True runs the jitted contains kernels on a pinned device
    copy with pow2 padding — answers must match the host path."""
    agg = TpuAggregator(capacity=1 << 12, batch_size=64)
    agg.ingest([(syncerts.stamp_serial(template, j), template.issuer_der)
                for j in range(33)])
    issuer_id, eh = _identity(template)
    idx = agg.registry.index_of_issuer_id(issuer_id)
    items = [(idx, eh, _serial_bytes(template, j)) for j in range(50)]
    host = capture_view(agg, epoch=1, device=False).lookup(items)
    dev = capture_view(agg, epoch=1, device=True).lookup(items)
    assert np.array_equal(host, dev)
    assert host[:33].all() and not host[33:].any()


def test_view_sharded_aggregator(template):
    """The sharded read view routes fingerprints to their home shard's
    row block — parity against the device-side global contains."""
    import jax
    from jax.sharding import Mesh

    from ct_mapreduce_tpu.agg.sharded_agg import ShardedAggregator

    mesh = Mesh(np.array(jax.devices()), ("shard",))
    agg = ShardedAggregator(mesh, capacity=1 << 12, batch_size=64)
    agg.ingest([(syncerts.stamp_serial(template, j), template.issuer_der)
                for j in range(64)])
    issuer_id, eh = _identity(template)
    idx = agg.registry.index_of_issuer_id(issuer_id)
    items = [(idx, eh, _serial_bytes(template, j)) for j in range(80)]
    view = capture_view(agg, epoch=1)
    assert view.n_shards == mesh.devices.size
    got = view.lookup(items)
    assert got[:64].all() and not got[64:].any()
    # Cross-check the routed host probe against the device global
    # contains on the same fingerprints.
    from ct_mapreduce_tpu.core import packing

    fps = np.array(
        [packing.fingerprint_host(idx, eh, _serial_bytes(template, j))
         for j in range(80)], np.uint32)
    assert np.array_equal(view.contains_fps(fps),
                          np.asarray(agg._device_contains(fps)))


def test_snapshot_manager_staleness_refresh(template):
    """A pool of one replica is the single-view manager: a fresh view
    is returned as is, ``refresh()`` raises the epoch, and a stale pool
    swaps in the background while it goes on serving."""
    agg = TpuAggregator(capacity=1 << 12, batch_size=64)
    mgr = ReplicaPool(agg, n_replicas=1, max_staleness_s=1000.0)
    v1 = mgr.view()  # the first capture is synchronous
    assert mgr.view() is v1  # fresh enough → same epoch
    v2 = mgr.refresh()
    assert v2.epoch == v1.epoch + 1
    assert mgr.view() is v2
    mgr.max_staleness_s = 0.0
    assert mgr.view().epoch >= v2.epoch  # served at once, never blocked
    deadline = time.time() + 60
    while mgr.stats()["snapshot_epoch"] <= v2.epoch \
            and time.time() < deadline:
        time.sleep(0.005)
    mgr.max_staleness_s = 1000.0  # the swap that landed is fresh enough
    assert mgr.view().epoch > v2.epoch  # stale → refreshed
    assert mgr.stats()["replicas"] == 1  # swapped, not grown


# -- the concurrency acceptance test --------------------------------------


@pytest.mark.parametrize("device", [True, False],
                         ids=["device-views", "host-views"])
def test_concurrent_ingest_query_consistency(template, device):
    """Ingest and query race for real: a writer thread feeds batches
    through a growing table (capacity starts at 1<<10 so grow-and-
    rehash fires mid-run) while reader threads query through a
    MembershipOracle with a tight staleness bound. Contract (round 12,
    epoch-honest): every answer surfaces its view's age, and a serial
    acked before that view's capture MUST read known — the replica
    pool's staggered refresh means serving never blocks on a capture
    (a mid-grow capture can take seconds while it waits on the fold
    lock), so staleness is surfaced rather than wall-clock-capped. A
    serial never fed must NEVER read known, at any epoch, and
    refreshes must actually keep landing (some answers fresh within
    the bound)."""
    agg = TpuAggregator(capacity=1 << 10, batch_size=64,
                        max_capacity=1 << 14, grow_at=0.55)
    issuer_idx = agg.registry.get_or_assign(template.issuer_der)
    _, eh = _identity(template)
    stale = 0.05
    oracle = MembershipOracle(agg, max_batch=256, max_delay_s=0.002,
                              max_staleness_s=stale, device=device)
    fresh_ages: list[float] = []
    epoch_walls: dict[int, float] = {}  # epoch -> capture-start wall
    acked: dict[int, float] = {}
    acked_lock = threading.Lock()
    stop = threading.Event()
    errors: list[str] = []
    n_batches, batch = 14, 64  # 896 lanes > 0.55 x 1024 ⇒ grow fires

    def writer():
        try:
            for b in range(n_batches):
                entries = [
                    (syncerts.stamp_serial(template, b * batch + j),
                     template.issuer_der)
                    for j in range(batch)
                ]
                agg.ingest(entries)  # returns ⇒ acked
                now = time.time()
                with acked_lock:
                    for j in range(batch):
                        acked[b * batch + j] = now
        except Exception as err:  # pragma: no cover - fails the test
            errors.append(f"writer: {err!r}")
        finally:
            stop.set()

    def reader(seed):
        r = np.random.default_rng(seed)
        while not stop.is_set() or r.integers(2) == 0:
            with acked_lock:
                known_now = dict(acked)
            if not known_now:
                time.sleep(0.001)
                continue
            js = list(known_now)
            pick = [js[int(r.integers(len(js)))] for _ in range(4)]
            ghosts = [int(r.integers(10**6, 2 * 10**6)) for _ in range(2)]
            items = [(issuer_idx, eh, _serial_bytes(template, j))
                     for j in pick + ghosts]
            try:
                res = oracle.query_raw(items)
            except Overloaded:
                continue
            # The authoritative capture instants: created_wall is
            # anchored at capture START (before the fold-lock wait),
            # so "acked before it" under-approximates "acked before
            # the lock was held" — the direction that keeps the check
            # sound under multi-second mid-grow captures.
            for rep in list(oracle.snapshots._replicas):
                epoch_walls[rep.epoch] = rep.created_wall
            for (known, epoch, age), j in zip(res, pick + ghosts):
                if j in known_now:
                    wall = epoch_walls.get(epoch)
                    if not known and wall is not None \
                            and known_now[j] < wall - 0.05:
                        errors.append(
                            f"acked serial {j} invisible in epoch "
                            f"{epoch}, captured "
                            f"{wall - known_now[j]:.3f}s after its ack")
                elif known:
                    errors.append(f"false positive: ghost serial {j}")
                if not stop.is_set():
                    fresh_ages.append(age)  # GIL-atomic append
            if stop.is_set():
                break

    w = threading.Thread(target=writer)
    readers = [threading.Thread(target=reader, args=(s,)) for s in (1, 2)]
    w.start()
    for t in readers:
        t.start()
    w.join(timeout=120)
    for t in readers:
        t.join(timeout=30)
    # Liveness: staggered refresh keeps landing — the pool advances
    # through multiple epochs instead of serving one ancient view
    # forever. How many land DURING the fixed-length load window is
    # box-speed-dependent (compile-inflated captures on a loaded
    # 1-core CI run can swallow most of it — observed round 17), so
    # the check is a bounded WAIT for the third epoch, not a snapshot
    # of whatever the window happened to reach: queries keep flowing
    # until the refresh machinery proves it is still advancing.
    deadline = time.time() + 60
    while (oracle.snapshots.stats()["snapshot_epoch"] < 3
           and time.time() < deadline):
        with contextlib.suppress(Overloaded):
            oracle.query_raw(
                [(issuer_idx, eh, _serial_bytes(template, 0))])
        time.sleep(0.05)
    pool_stats = oracle.snapshots.stats()
    oracle.close()
    assert not errors, errors[:10]
    assert agg.metrics.get("overflow", 0) >= 0  # table survived
    # The run really exercised growth (the mid-grow torn-read hazard).
    assert agg.capacity > 1 << 10, "table never grew; raise n_batches"
    assert fresh_ages, "no answers recorded"
    assert pool_stats["snapshot_epoch"] >= 3, pool_stats
    assert pool_stats["replicas"] >= 2, pool_stats
    assert pool_stats["replica_device"] == [device] * 2, pool_stats
    # And the final state is complete: every fed serial present, by
    # the host mirror (the reference).
    final = capture_view(agg, epoch=99)
    items = [(issuer_idx, eh, _serial_bytes(template, j))
             for j in range(n_batches * batch)]
    assert final.lookup(items).all()


# -- HTTP server ----------------------------------------------------------


def _post(url, payload, timeout=10):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def test_query_server_http_api(template):
    agg = TpuAggregator(capacity=1 << 12, batch_size=64)
    agg.ingest([(syncerts.stamp_serial(template, j), template.issuer_der)
                for j in range(20)])
    issuer_id, eh = _identity(template)
    exp_id = ExpDate.from_unix_hour(eh).id()
    srv = QueryServer(agg, 0, host="127.0.0.1",
                      max_delay_s=0.001).start()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        # Bulk query: present + absent, epoch and staleness surfaced.
        queries = [{"issuer": issuer_id, "expDate": exp_id,
                    "serial": _serial_bytes(template, j).hex()}
                   for j in (0, 5, 19, 777)]
        code, body = _post(f"{base}/query", {"queries": queries})
        assert code == 200
        assert [r["known"] for r in body["results"]] == [
            True, True, True, False]
        assert body["epoch"] >= 1 and body["staleness_s"] >= 0
        # Single-query shorthand.
        code, body = _post(f"{base}/query", {
            "issuer": issuer_id, "expDate": exp_id,
            "serial": _serial_bytes(template, 5).hex()})
        assert code == 200 and body["known"] is True
        # Unknown issuer: honest False.
        code, body = _post(f"{base}/query", {
            "issuer": "nosuchissuer=", "expDate": exp_id,
            "serial": "4d00"})
        assert code == 200 and body["known"] is False
        # Malformed: 400, not a 500.
        for bad in ({"queries": []},
                    {"issuer": issuer_id, "expDate": exp_id,
                     "serial": "zz"},
                    {"issuer": issuer_id, "expDate": "June 15",
                     "serial": "4d00"}):
            req = urllib.request.Request(
                f"{base}/query", data=json.dumps(bad).encode(),
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=10)
            assert ei.value.code == 400
        # Issuer metadata.
        from urllib.parse import quote

        with urllib.request.urlopen(
                f"{base}/issuer/{quote(issuer_id, safe='')}",
                timeout=10) as resp:
            meta = json.loads(resp.read())
        assert meta["unknown_total"] == 20
        assert meta["dns"] == 1 and meta["crls"] == 1
        assert "staleness_s" in meta
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/issuer/doesnotexist",
                                   timeout=10)
        assert ei.value.code == 404
        # Health: queue + snapshot numbers.
        with urllib.request.urlopen(f"{base}/healthz", timeout=10) as resp:
            h = json.loads(resp.read())
        assert h["healthy"] and h["queue_cap"] > 0
        assert h["snapshot_epoch"] >= 1
    finally:
        srv.stop()


def test_query_server_sheds_with_429(template):
    """Overload answers 429 overloaded — never an unbounded queue."""
    agg = TpuAggregator(capacity=1 << 12, batch_size=64)
    agg.ingest([(syncerts.stamp_serial(template, 0), template.issuer_der)])
    issuer_id, eh = _identity(template)
    exp_id = ExpDate.from_unix_hour(eh).id()
    srv = QueryServer(agg, 0, host="127.0.0.1", max_queue_lanes=2,
                      max_delay_s=0.001).start()
    try:
        # A 3-lane request cannot be admitted into a 2-lane queue.
        q = {"issuer": issuer_id, "expDate": exp_id,
             "serial": _serial_bytes(template, 0).hex()}
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/query",
            data=json.dumps({"queries": [q, q, q]}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 429
        assert json.loads(ei.value.read())["error"] == "overloaded"
        # The plane still answers admissible requests afterwards.
        code, body = _post(f"http://127.0.0.1:{srv.port}/query", q)
        assert code == 200 and body["known"] is True
    finally:
        srv.stop()


def test_query_server_getcert_proxy():
    """/getcert proxies one log entry as PEM (ct-getcert's routed
    path), using the server's transport override."""
    from tests.fakelog import FakeLog
    from tests import certgen
    import datetime

    log = FakeLog()
    future = datetime.datetime(2031, 6, 15, tzinfo=datetime.timezone.utc)
    issuer_der = certgen.make_cert(serial=1, issuer_cn="Proxy CA",
                                   is_ca=True, not_after=future)
    leaf = certgen.make_cert(serial=1000, issuer_cn="Proxy CA",
                             subject_cn="proxy.example.com", is_ca=False,
                             not_after=future)
    log.add_cert(leaf, issuer_der, timestamp_ms=1700000000000)
    agg = TpuAggregator(capacity=1 << 10, batch_size=64)
    srv = QueryServer(agg, 0, host="127.0.0.1",
                      transport=log.transport).start()
    try:
        from urllib.parse import urlencode

        qs = urlencode({"log": log.url, "index": 0})
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/getcert?{qs}",
                timeout=10) as resp:
            body = json.loads(resp.read())
        assert body["pem"].startswith("-----BEGIN CERTIFICATE-----")
        with pytest.raises(urllib.error.HTTPError) as ei:
            qs = urlencode({"log": log.url, "index": 99})
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/getcert?{qs}", timeout=10)
        assert ei.value.code in (404, 502)
    finally:
        srv.stop()


def test_serve_batch_spans_recorded(template):
    """serve.batch spans carry lane counts — what batching
    effectiveness is read from."""
    from ct_mapreduce_tpu.telemetry import trace

    tracer = trace.enable()
    t0 = tracer.now_us()
    try:
        agg = TpuAggregator(capacity=1 << 12, batch_size=64)
        agg.ingest([(syncerts.stamp_serial(template, j),
                     template.issuer_der) for j in range(8)])
        issuer_id, eh = _identity(template)
        idx = agg.registry.index_of_issuer_id(issuer_id)
        oracle = MembershipOracle(agg, max_batch=64, max_delay_s=0.01)
        threads = [threading.Thread(
            target=lambda j=j: oracle.query_raw(
                [(idx, eh, _serial_bytes(template, j))])
        ) for j in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        oracle.close()
        spans = [e for e in tracer.events()
                 if e.get("ph") == "X" and e["name"] == "serve.batch"
                 and e["ts"] >= t0]
        assert spans, "no serve.batch spans recorded"
        lanes = sum(e["args"]["lanes"] for e in spans)
        assert lanes == 8
        waits = [e for e in tracer.events()
                 if e.get("ph") == "X" and e["name"] == "serve.wait"
                 and e["ts"] >= t0]
        assert len(waits) == 8
    finally:
        trace.disable()


def test_a_connections_whole_life_is_one_front_conn_span(template):
    """``front.conn`` covers a connection from its accept to the
    socket's close: one span a connection, recorded by the front's one
    thread, the parent of the ``serve.wait`` its request causes, with
    what the connection carried and the CPU of its turns, summed; over a
    kept connection the requests add up under one span. The front closes
    after every answer unless the client asks to keep the connection."""
    import http.client

    from ct_mapreduce_tpu.telemetry import trace

    agg = TpuAggregator(capacity=1 << 12, batch_size=64)
    agg.ingest([(syncerts.stamp_serial(template, j), template.issuer_der)
                for j in range(1, 6)])
    issuer_id, eh = _identity(template)
    # A serial a request: one asked twice is answered from the cache,
    # with no wait in the batcher's queue.
    bodies = [json.dumps({"issuer": issuer_id,
                          "expDate": ExpDate.from_unix_hour(eh).id(),
                          "serial": _serial_bytes(template, j).hex()}).encode()
              for j in range(1, 6)]
    body = bodies[0]
    assert {len(b) for b in bodies} == {len(body)}
    srv = QueryServer(agg, 0, host="127.0.0.1").start()
    tracer = trace._tracer = trace.SpanTracer(ring_size=4096)

    def conns():
        return [e for e in tracer.events() if e.get("name") == "front.conn"]

    def settled(n):  # the span closes after the client has its answer
        deadline = time.monotonic() + 10
        while len(conns()) < n and time.monotonic() < deadline:
            time.sleep(0.01)
        return conns()

    try:
        answers = []
        for one in bodies[:3]:
            conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
            conn.request("POST", "/query", one,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.version == 10 and resp.will_close
            answers.append(resp.read())
            conn.close()
        spans = settled(3)
        assert len(spans) == 3 and len({e["id"] for e in spans}) == 3
        waits = [e for e in tracer.events() if e.get("name") == "serve.wait"]
        assert sorted(w["parent"] for w in waits) \
            == sorted(e["id"] for e in spans)
        names = {m["tid"]: m["args"]["name"] for m in tracer.events()
                 if m.get("ph") == "M"}
        for e, answer in zip(sorted(spans, key=lambda e: e["ts"]), answers):
            assert e["parent"] == 0 and e["cat"] == "front"
            assert names[e["tid"]] == "query-front"
            assert e["args"] == {"requests": 1, "bytes_in": len(body),
                                 "bytes_out": len(answer)}
            assert 0 < e["tdur"] <= e["dur"] + 50.0
            (wait,) = [w for w in waits if w["parent"] == e["id"]]
            assert wait["tid"] == e["tid"] and wait["args"] == {"lanes": 1}
            assert e["ts"] <= wait["ts"] and wait["dur"] <= e["dur"]
            assert wait["tdur"] == 0.0  # nobody is parked in it
        # Kept alive: two queries and a 404 over one connection.
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
        sent = 0
        for path, one in zip(("/query", "/query", "/nowhere"), bodies[3:] * 2):
            conn.request("POST", path, one,
                         {"Content-Type": "application/json",
                          "Connection": "keep-alive"})
            resp = conn.getresponse()
            sent += len(resp.read())
            assert resp.status == (200 if path == "/query" else 404)
            assert resp.version == 11 and not resp.will_close
        conn.close()
        (kept,) = settled(4)[3:]
        assert kept["args"] == {"requests": 3, "bytes_in": 3 * len(body),
                                "bytes_out": sent}
        assert sum(1 for w in tracer.events()
                   if w.get("name") == "serve.wait"
                   and w["parent"] == kept["id"]) == 2
        # tdur is the CPU of the connection's turns on the one front
        # thread: all of them together cannot exceed what that thread
        # used, and the kept connection's three requests cost more than
        # none.
        front = [e for e in tracer.events() if e.get("name") == "front.conn"]
        assert len({e["tid"] for e in front}) == 1
        ordered = sorted(front, key=lambda e: e["tts"])
        assert sum(e["tdur"] for e in front) <= (
            ordered[-1]["tts"] + ordered[-1]["tdur"] - ordered[0]["tts"] + 1.0)
        assert kept["tdur"] > 0
    finally:
        trace._tracer = None
        srv.stop()
    # Tracer off: the connection is served as before, under no span.
    assert trace.span("front.conn") is trace._NULL_SPAN


def test_device_replicas_answer_through_the_jitted_contains(template):
    """A device oracle with two replicas, read off its own spans: every
    ``serve.lookup`` ran in device mode, both replicas took batches in
    turn, the membership program itself ran (``serve.contains_device``)
    and nothing slid onto the host mirror."""
    from ct_mapreduce_tpu.telemetry import metrics as tmetrics
    from ct_mapreduce_tpu.telemetry import trace

    agg = TpuAggregator(capacity=1 << 12, batch_size=64)
    agg.ingest([(syncerts.stamp_serial(template, j), template.issuer_der)
                for j in range(32)])
    issuer_id, eh = _identity(template)
    idx = agg.registry.index_of_issuer_id(issuer_id)
    sink = tmetrics.InMemSink()
    prev = tmetrics.get_sink()
    tmetrics.set_sink(sink)
    tracer = trace.enable()
    t0 = tracer.now_us()
    try:
        oracle = MembershipOracle(agg, max_batch=64, max_delay_s=0.001,
                                  max_staleness_s=1e9, device=True,
                                  replicas=2, cache_size=0)
        try:
            oracle.snapshots.warm()
            for j in range(8):  # one batch a call: the views alternate
                known, ghost = oracle.query_raw([
                    (idx, eh, _serial_bytes(template, j)),
                    (idx, eh, _serial_bytes(template, 10_000 + j))])
                assert known[0] is True and ghost[0] is False
        finally:
            oracle.close()
        spans = [e for e in tracer.events()
                 if e.get("ph") == "X" and e["ts"] >= t0]
    finally:
        trace.disable()
        tmetrics.set_sink(prev)
    lookups = [e for e in spans if e["name"] == "serve.lookup"]
    assert len(lookups) == 8
    assert all(e["args"]["device"] == 1 for e in lookups)
    assert {e["args"]["replica"] for e in lookups} == {0, 1}
    contains = [e for e in spans if e["name"] == "serve.contains_device"]
    assert len(contains) == 8
    assert not any(e["name"] == "serve.contains_host" for e in spans)
    assert sink.snapshot()["counters"].get("serve.device_fallback", 0) == 0


# -- replica pool (round 12) ----------------------------------------------


@pytest.mark.parametrize("device", [True, False],
                         ids=["device-views", "host-views"])
def test_replica_pool_mixed_epoch_parity_fuzz(template, device):
    """N replicas at MIXED epochs through table growth must agree with
    the serial truth set: on every replica, every serial acked before
    that replica's capture reads known, and ghosts read absent at
    every epoch (the ISSUE 7 parity-fuzz acceptance)."""
    agg = TpuAggregator(capacity=1 << 10, batch_size=64,
                        max_capacity=1 << 14, grow_at=0.55)
    issuer_idx = agg.registry.get_or_assign(template.issuer_der)
    _, eh = _identity(template)
    pool = ReplicaPool(agg, n_replicas=3, max_staleness_s=1e9,
                       device=device)
    rng = np.random.default_rng(7)
    acked = 0
    truth_at_capture: dict[int, int] = {}
    mirrors = {}  # epoch -> a host mirror of the same instant
    for _stage in range(6):  # 576 lanes through a 1<<10 table ⇒ grows
        agg.ingest([
            (syncerts.stamp_serial(template, acked + i),
             template.issuer_der)
            for i in range(96)
        ])
        acked += 96
        v = pool.refresh()  # staggered: swaps exactly ONE replica
        truth_at_capture[v.epoch] = acked
        mirrors[v.epoch] = capture_view(agg, epoch=v.epoch)
    assert agg.capacity > 1 << 10, "table never grew"
    reps = list(pool._replicas)
    assert len(reps) == 3
    assert len({r.epoch for r in reps}) == 3, "epochs not mixed"
    for r in reps:
        n_known = truth_at_capture[r.epoch]
        pick = [int(j) for j in rng.integers(0, acked, size=48)]
        ghosts = [int(j) for j in rng.integers(10**6, 2 * 10**6, size=16)]
        items = [(issuer_idx, eh, _serial_bytes(template, j))
                 for j in pick + ghosts]
        got = r.lookup(items)
        assert r._device is device
        assert np.array_equal(got, mirrors[r.epoch].lookup(items))
        for k, j in enumerate(pick):
            if j < n_known:
                assert got[k], (
                    f"epoch {r.epoch}: serial {j} acked before capture "
                    f"(truth {n_known}) reads absent")
        assert not got[len(pick):].any(), f"ghost hit at epoch {r.epoch}"
    # Round-robin serving rotates through every live replica.
    served = {pool.view().epoch for _ in range(9)}
    assert served == {r.epoch for r in reps}
    # floor_epoch is the oldest live epoch (the cache validity horizon).
    assert pool.floor_epoch() == min(r.epoch for r in reps)


def test_replica_pool_shard_routed_block_pinning(template):
    """On a multi-device mesh a replica is the row-sharded copy itself,
    each shard's row block on its shard's own device — never the full
    global rows on one chip — and the shard-routed probe answers with
    exact parity."""
    import jax
    from jax.sharding import Mesh

    from ct_mapreduce_tpu.agg.sharded_agg import ShardedAggregator

    mesh = Mesh(np.array(jax.devices()), ("shard",))
    agg = ShardedAggregator(mesh, capacity=1 << 12, batch_size=64)
    agg.ingest([(syncerts.stamp_serial(template, j), template.issuer_der)
                for j in range(64)])
    issuer_id, eh = _identity(template)
    idx = agg.registry.index_of_issuer_id(issuer_id)
    pool = ReplicaPool(agg, n_replicas=2, max_staleness_s=1e9,
                       device=True).warm()
    devs = jax.devices()
    for v in pool._replicas:
        assert v.n_shards == mesh.devices.size
        assert v._dev_rows is not None, "replica holds no device copy"
        assert v._dev_state is None  # the one-chip probe state
        assert v.rows is None, "a device replica made a host array"
        block = v.n_rows // v.n_shards
        shards = sorted(v._dev_rows.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        assert len(shards) == v.n_shards
        for s, shard in enumerate(shards):
            assert shard.data.shape[0] == block
            assert shard.device == devs[s % len(devs)]
    items = [(idx, eh, _serial_bytes(template, j)) for j in range(80)]
    for v in pool._replicas:
        got = v.lookup(items)
        assert got[:64].all() and not got[64:].any()
        # Device parity against the pure-host routed mirror.
        host = capture_view(agg, epoch=99).lookup(items)
        assert np.array_equal(got, host)


# -- the query plane on a mesh of four: a batch goes to its shards once -----

MESH_CHIPS = 4
FED = 768  # serials through the device lane, on the mesh and on one chip
HOST_LANE = range(5000, 5008)  # serials landed in the exact host lane
WIDTHS = (16, 32, 64, 128, 256, 512, 1024, 2048, 4096)  # a cell's warmup_lanes


def _mesh4():
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:MESH_CHIPS]), ("shard",))


@pytest.fixture(scope="module")
def fed_mesh_and_chip(template):
    """The same serials through a ``ShardedAggregator`` on a mesh of
    four and through a one-chip ``TpuAggregator``, a few of them into
    the exact host lane; and the plain reference: a ``set`` of every
    ``(issuer, expDate, serial)`` fed."""
    from ct_mapreduce_tpu.agg.sharded_agg import ShardedAggregator

    issuer_id, eh = _identity(template)
    aggs = (ShardedAggregator(_mesh4(), capacity=1 << 13, batch_size=256),
            TpuAggregator(capacity=1 << 13, batch_size=256))
    fed = set()
    for agg in aggs:
        agg.ingest([(syncerts.stamp_serial(template, j), template.issuer_der)
                    for j in range(FED)])
        idx = agg.registry.get_or_assign(template.issuer_der)
        for j in HOST_LANE:
            fields = hostder.parse_cert(syncerts.stamp_serial(template, j))
            agg._host_dedup(fields, idx, fields.not_after_unix_hour)
    for j in [*range(FED), *HOST_LANE]:
        fed.add((issuer_id, eh, _serial_bytes(template, j)))
    assert aggs[0].dedup.n_shards == MESH_CHIPS
    return aggs, fed


def _mixed_batch(rng, lanes: int) -> list[int]:
    """Serial numbers of a batch: fed ones, never-fed ones, host-lane
    ones, drawn with replacement (so a large batch repeats many)."""
    pools = (np.arange(FED), np.arange(10**6, 10**6 + 4 * FED),
             np.array(HOST_LANE))
    which = rng.choice(3, size=lanes, p=(0.6, 0.3, 0.1))
    picks = [int(rng.choice(pools[w])) for w in which]
    if lanes >= 2:
        picks[-1] = picks[0]  # a duplicate in every batch that has room
    return picks


@pytest.mark.parametrize("lanes", [1, 2, 17, 4096])
def test_sharded_device_view_equals_set_mirror_and_one_chip(
        template, fed_mesh_and_chip, lanes):
    """Membership has no tolerance: the sharded device view answers a
    mixed batch (known, never fed, host-lane, duplicates) lane for lane
    as the plain ``set``, as its own host mirror and as the one-chip
    aggregator fed the same serials."""
    (mesh_agg, chip_agg), fed = fed_mesh_and_chip
    issuer_id, eh = _identity(template)
    rng = np.random.default_rng([20261001, lanes])
    serials = [_serial_bytes(template, j)
               for j in _mixed_batch(rng, lanes)]
    want = np.array([(issuer_id, eh, sb) in fed for sb in serials])
    if lanes >= 17:
        assert want.any() and not want.all()
    answers = {}
    for name, agg, device in (("mesh device view", mesh_agg, True),
                              ("mesh host mirror", mesh_agg, False),
                              ("one chip", chip_agg, True)):
        idx = agg.registry.index_of_issuer_id(issuer_id)
        view = capture_view(agg, epoch=1, device=device)
        assert view._device is device
        answers[name] = view.lookup([(idx, eh, sb) for sb in serials])
    for name, got in answers.items():
        assert np.array_equal(got, want), name


def test_sharded_host_lane_guard_probes_each_shard_on_its_own_chip(
        template, fed_mesh_and_chip):
    """``ShardedAggregator._device_contains`` (the host lane's guard)
    routes on the host and probes under ``shard_map``, under the table
    lock: equal to the host mirror on fed and never-fed fingerprints."""
    from ct_mapreduce_tpu.core import packing

    (mesh_agg, _chip), _fed = fed_mesh_and_chip
    _issuer_id, eh = _identity(template)
    idx = mesh_agg.registry.get_or_assign(template.issuer_der)
    fps = np.array([packing.fingerprint_host(idx, eh,
                                             _serial_bytes(template, j))
                    for j in [*range(0, FED, 7), *range(10**6, 10**6 + 50)]],
                   np.uint32)
    got = mesh_agg._device_contains(fps)
    mirror = capture_view(mesh_agg, epoch=1, device=False)
    assert np.array_equal(got, mirror._contains_host(fps))
    assert got[: len(range(0, FED, 7))].all() and not got[-50:].any()
    assert mesh_agg._device_contains(np.zeros((0, 4), np.uint32)).shape == (0,)


def _fps_to_shard(rng, lanes: int, shard: int) -> np.ndarray:
    """Random fingerprints that all hash to one shard of four."""
    from ct_mapreduce_tpu.agg.sharded import shard_of_np

    out = np.zeros((0, 4), np.uint32)
    while out.shape[0] < lanes:
        fps = rng.integers(1, 1 << 32, size=(8 * lanes, 4), dtype=np.uint32)
        out = np.concatenate([out, fps[shard_of_np(fps, MESH_CHIPS) == shard]])
    return out[:lanes]


@pytest.mark.parametrize("seed", [11, 2147488905])
def test_no_probe_compiles_after_warm_up_whatever_the_seed(
        fed_mesh_and_chip, seed):
    """The set of probe programs is fixed by the widths warmed, not by
    the draw: after one batch a width (as a cell's generator sends
    them) 200 seeded batches of 1-4,096 lanes, among them batches whose
    every lane hashes to one shard, compile nothing."""
    import jax.monitoring

    (mesh_agg, _chip), _fed = fed_mesh_and_chip
    compiled: list[str] = []
    listening = [True]

    def on_compile(event: str, _seconds: float, **_kw) -> None:
        if listening[0] and event.endswith("backend_compile_duration"):
            compiled.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        rng = np.random.default_rng(seed)
        views = [capture_view(mesh_agg, epoch=e, device=True) for e in (1, 2)]
        for width in WIDTHS:  # the warm-up: one view is enough
            fps = rng.integers(1, 1 << 32, size=(width, 4), dtype=np.uint32)
            assert not views[0].contains_fps(fps).any()
        compiled.clear()
        for k in range(200):
            lanes = int(2 ** rng.uniform(0, 12))
            if k % 25 == 0:
                fps = _fps_to_shard(rng, lanes, shard=k // 25 % MESH_CHIPS)
            else:
                fps = rng.integers(1, 1 << 32, size=(lanes, 4),
                                   dtype=np.uint32)
            assert views[k % 2].contains_fps(fps).shape == (lanes,)
        assert views[0].contains_fps(
            _fps_to_shard(rng, 4096, shard=3)).shape == (4096,)
        assert compiled == []
    finally:
        listening[0] = False


def test_mesh_view_says_the_qshard_family_every_batch(
        template, fed_mesh_and_chip):
    """Span ``qshard.probe`` [lanes, shards, width, replica] round the
    one dispatch, the five counters on every batch (a batch with no
    device-eligible lane says them by 0), ``snapshot.capture`` says
    ``shards``; a one-chip view says none of the family."""
    from ct_mapreduce_tpu.telemetry import metrics as tmetrics
    from ct_mapreduce_tpu.telemetry import trace

    (mesh_agg, chip_agg), _fed = fed_mesh_and_chip
    issuer_id, eh = _identity(template)
    sink = tmetrics.InMemSink()
    prev = tmetrics.get_sink()
    tmetrics.set_sink(sink)
    prev_tracer = trace._tracer
    tracer = trace._tracer = trace.SpanTracer(ring_size=4096)
    try:
        pool = ReplicaPool(mesh_agg, n_replicas=1, max_staleness_s=1e9,
                           device=True).warm()
        view = pool.view()
        idx = mesh_agg.registry.index_of_issuer_id(issuer_id)
        known = [(idx, eh, _serial_bytes(template, j)) for j in range(20)]
        host_lane = [(idx, eh, _serial_bytes(template, HOST_LANE[0]))]
        assert view.lookup(known + host_lane).all()
        counters = sink.snapshot()["counters"]
        assert {k: v for k, v in counters.items()
                if k.startswith("qshard.")} == {
            "qshard.batches": 1.0, "qshard.device_calls": 1.0,
            "qshard.lanes": 21.0,
            "qshard.padded_lanes": MESH_CHIPS * 32 - 21.0,
            "qshard.host_lane_hits": 1.0}
        # No lane a device could hold: the batch says the family by 0.
        assert not view.lookup([(idx, eh, b"\x01" * 64)]).any()
        counters = sink.snapshot()["counters"]
        assert counters["qshard.batches"] == 2.0
        assert counters["qshard.device_calls"] == 1.0
        spans = {e["name"]: e for e in tracer.events() if e.get("ph") == "X"}
        probe = spans["qshard.probe"]["args"]
        assert (probe["lanes"], probe["width"], probe["replica"]) == (21, 32, 0)
        assert 1 <= probe["shards"] <= MESH_CHIPS
        assert spans["snapshot.capture"]["args"]["shards"] == MESH_CHIPS
        # One chip: its own program and span, nothing of the family.
        before = dict(sink.snapshot()["counters"])
        idx1 = chip_agg.registry.index_of_issuer_id(issuer_id)
        assert capture_view(chip_agg, epoch=1, device=True).lookup(
            [(idx1, eh, _serial_bytes(template, 3))]).all()
        after = sink.snapshot()["counters"]
        assert {k: v for k, v in after.items() if k.startswith("qshard.")} \
            == {k: v for k, v in before.items() if k.startswith("qshard.")}
    finally:
        trace._tracer = prev_tracer
        tmetrics.set_sink(prev)


@pytest.mark.parametrize("where", ["one chip", "mesh of four"])
def test_lookup_answers_the_same_with_the_native_fingerprint_and_without(
        template, fed_mesh_and_chip, monkeypatch, where):
    """``TableView.lookup`` keys its eligible lanes with
    ``fingerprints_np``: one native call (PR 44), or the NumPy routine
    where the library is older than the symbol. Known, never-fed,
    host-lane, oversize-serial and unknown-issuer items answer the same
    either way and as the plain ``set``; ``fp.lanes`` counts the
    eligible lanes and ``fp.fallback_lanes`` says which routine keyed
    them; on the mesh the ``qshard.`` family still says all five every
    batch."""
    from ct_mapreduce_tpu import native
    from ct_mapreduce_tpu.core import packing
    from ct_mapreduce_tpu.telemetry import metrics as tmetrics

    if not getattr(native.load(), "has_fp", False):
        pytest.skip("native library unavailable")
    (mesh_agg, chip_agg), fed = fed_mesh_and_chip
    agg = chip_agg if where == "one chip" else mesh_agg
    issuer_id, eh = _identity(template)
    idx = agg.registry.index_of_issuer_id(issuer_id)
    known = [(idx, eh, _serial_bytes(template, j)) for j in (0, 5, FED - 1)]
    never = [(idx, eh, _serial_bytes(template, 10**6 + j)) for j in range(2)]
    host_lane = [(idx, eh, _serial_bytes(template, HOST_LANE[0]))]
    oversize = [(idx, eh, b"\x01" * (packing.MAX_SERIAL_BYTES + 1))]
    stranger = [(-1, eh, _serial_bytes(template, 0))]
    items = known + never + host_lane + oversize + stranger
    want = np.array([(issuer_id, eh, sb) in fed and i >= 0
                     for i, _eh, sb in items])
    assert want.tolist() == [True] * 3 + [False] * 2 + [True] + [False] * 2
    eligible = len(known + never + host_lane)
    view = capture_view(agg, epoch=1, device=True)
    prev = tmetrics.get_sink()
    said = {}
    try:
        for how in ("native", "numpy"):
            if how == "numpy":
                monkeypatch.setattr(native.load(), "has_fp", False)
            sink = tmetrics.InMemSink()
            tmetrics.set_sink(sink)
            assert np.array_equal(view.lookup(items), want), how
            # No lane a device could hold: no fingerprint, no call.
            assert not view.lookup(oversize + stranger).any()
            said[how] = sink.snapshot()["counters"]
    finally:
        tmetrics.set_sink(prev)
    assert said["native"]["fp.lanes"] == said["numpy"]["fp.lanes"] == eligible
    assert said["native"]["fp.fallback_lanes"] == 0.0
    assert said["numpy"]["fp.fallback_lanes"] == eligible
    family = {k: v for k, v in said["native"].items()
              if k.startswith("qshard.")}
    assert family == {k: v for k, v in said["numpy"].items()
                      if k.startswith("qshard.")}
    if where == "one chip":
        assert family == {}
    else:
        assert family == {
            "qshard.batches": 2.0, "qshard.device_calls": 1.0,
            "qshard.lanes": float(eligible),
            "qshard.padded_lanes": MESH_CHIPS * 16.0 - eligible,
            "qshard.host_lane_hits": 1.0}


def _fail(msg):
    def boom(*_a, **_k):
        raise RuntimeError(msg)
    return boom


@pytest.mark.parametrize("where", ["copy", "probe"])
def test_view_device_fallback_to_host(template, monkeypatch, where):
    """Off the TPU, a view whose device copy cannot be made (the
    failure is injected at ``snapshot_copy``), or whose copy stops
    answering (at the membership kernel), becomes a host mirror
    (serve.device_fallback) instead of failing the batch."""
    from ct_mapreduce_tpu.ops import buckettable, hashtable
    from ct_mapreduce_tpu.serve import snapshot as snapmod
    from ct_mapreduce_tpu.telemetry import metrics as tmetrics

    agg = TpuAggregator(capacity=1 << 12, batch_size=64)
    agg.ingest([(syncerts.stamp_serial(template, j), template.issuer_der)
                for j in range(10)])
    issuer_id, eh = _identity(template)
    idx = agg.registry.index_of_issuer_id(issuer_id)
    sink = tmetrics.InMemSink()
    prev = tmetrics.get_sink()
    tmetrics.set_sink(sink)
    try:
        if where == "copy":
            monkeypatch.setattr(snapmod, "snapshot_copy",
                                _fail("no device"))
            view = capture_view(agg, epoch=1, device=True)
        else:
            view = capture_view(agg, epoch=1, device=True)
            assert view._device is True and view.rows is None
            for mod in (hashtable, buckettable):  # whichever layout
                monkeypatch.setattr(mod, "contains", _fail("torn down"))
        items = [(idx, eh, _serial_bytes(template, j)) for j in range(12)]
        got = view.lookup(items)
        assert got[:10].all() and not got[10:].any()
        assert view._device is False  # latched to the host path
        assert view.rows is not None and view._dev_rows is None
        counters = sink.snapshot()["counters"]
        assert counters.get("serve.device_fallback", 0) >= 1
        # Subsequent lookups answer from the host mirror directly.
        assert view.lookup(items[:3]).all()
    finally:
        tmetrics.set_sink(prev)


def test_view_device_pin_failure_is_an_error_on_tpu(template, monkeypatch):
    """On a TPU backend a replica whose copy cannot land raises instead
    of sliding onto the host mirror (serve.device_fallback stays 0),
    and the pool's next capture takes the device again."""
    import jax
    from ct_mapreduce_tpu.serve import snapshot as snapmod
    from ct_mapreduce_tpu.telemetry import metrics as tmetrics

    agg = TpuAggregator(capacity=1 << 12, batch_size=64)
    agg.ingest([(syncerts.stamp_serial(template, j), template.issuer_der)
                for j in range(4)])
    issuer_id, eh = _identity(template)
    idx = agg.registry.index_of_issuer_id(issuer_id)
    sink = tmetrics.InMemSink()
    prev = tmetrics.get_sink()
    tmetrics.set_sink(sink)
    try:
        pool = ReplicaPool(agg, n_replicas=1, device=True)
        real_copy = snapmod.snapshot_copy
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(snapmod, "snapshot_copy",
                            _fail("RESOURCE_EXHAUSTED"))
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            pool.view()
        assert pool.stats()["replicas"] == 0  # no host mirror adopted
        assert pool.refresh_in_flight is False
        monkeypatch.setattr(snapmod, "snapshot_copy", real_copy)
        view = pool.view()
        assert view._device is True  # not latched to the host mirror
        assert view.lookup([(idx, eh, _serial_bytes(template, 0))])[0]
        counters = sink.snapshot()["counters"]
        assert counters.get("serve.device_fallback", 0) == 0
    finally:
        tmetrics.set_sink(prev)


def test_device_view_holds_no_host_rows(template):
    """A device view of an unsharded aggregator is a copy of the live
    table ON the device: no host array of the table exists after the
    capture, nor after serving (the rows never cross the host link)."""
    agg = TpuAggregator(capacity=1 << 12, batch_size=64)
    agg.ingest([(syncerts.stamp_serial(template, j), template.issuer_der)
                for j in range(20)])
    issuer_id, eh = _identity(template)
    idx = agg.registry.index_of_issuer_id(issuer_id)
    items = [(idx, eh, _serial_bytes(template, j)) for j in range(30)]
    pool = ReplicaPool(agg, n_replicas=2, max_staleness_s=1e9,
                       device=True).warm()
    for v in list(pool._replicas) + [capture_view(agg, 9, device=True)]:
        assert v._device is True and v.rows is None
        assert v._dev_rows is not agg.table.rows  # a copy, not the table
        assert v._dev_rows.shape == agg.table.rows.shape
        assert v.capacity == agg.capacity and v.n_rows > 0
        got = v.lookup(items)
        assert got[:20].all() and not got[20:].any()
        assert v._device is True and v.rows is None  # after serving too
    # The host mirror is what device=False selects, and only that.
    host = capture_view(agg, epoch=10, device=False)
    assert isinstance(host.rows, np.ndarray) and host._dev_rows is None
    assert np.array_equal(host.rows, np.asarray(pool.view()._dev_rows))


@pytest.mark.parametrize("device", [True, False],
                         ids=["device-view", "host-mirror"])
def test_view_outlives_steps_and_growth(template, device):
    """A view answers as of its capture however far the live table
    moves on: further batches step (and, on an accelerator, donate)
    the buffer the copy was taken from, and grow-and-rehash replaces
    it. A serial fed after the capture reads unknown there and known
    in the next epoch."""
    agg = TpuAggregator(capacity=1 << 10, batch_size=64,
                        max_capacity=1 << 14, grow_at=0.55)
    issuer_idx = agg.registry.get_or_assign(template.issuer_der)
    _, eh = _identity(template)

    def feed(lo, hi):
        for j0 in range(lo, hi, 64):
            agg.ingest([(syncerts.stamp_serial(template, j),
                         template.issuer_der) for j in range(j0, j0 + 64)])

    feed(0, 128)
    view = capture_view(agg, epoch=1, device=device).pin()
    cap0 = agg.capacity
    feed(128, 896)  # 896 lanes > 0.55 x 1024 ⇒ grow fires
    assert agg.capacity > cap0, "table never grew"
    items = [(issuer_idx, eh, _serial_bytes(template, j))
             for j in range(896)]
    got = view.lookup(items)
    assert view._device is device
    assert got[:128].all(), "acked before the capture, unknown in it"
    assert not got[128:].any(), "fed after the capture, known in it"
    assert view.capacity == cap0
    nxt = capture_view(agg, epoch=2, device=device).pin()
    assert nxt.lookup(items).all()
    assert not nxt.lookup(
        [(issuer_idx, eh, _serial_bytes(template, 10**6))])[0]


# -- hot-serial cache ------------------------------------------------------


def test_hot_serial_cache_unit():
    """Epoch-floor validity + LRU bound + no answer downgrades."""
    c = HotSerialCache(capacity=2)
    c.put(("a",), known=False, epoch=1, created_wall=0.0)
    assert c.get(("a",), floor_epoch=1).known is False  # hit after miss
    # Floor bump (every replica refreshed past epoch 1) ⇒ the entry is
    # unreachable and evicted on probe — no ghost answers across epochs.
    assert c.get(("a",), floor_epoch=2) is None
    assert c.get(("a",), floor_epoch=1) is None
    c.put(("a",), True, 3, 0.0)
    c.put(("b",), True, 3, 0.0)
    c.put(("c",), True, 3, 0.0)
    assert len(c) == 2  # LRU bound holds
    assert c.get(("a",), 3) is None  # oldest evicted
    c.put(("b",), False, 2, 0.0)  # older epoch must not downgrade
    assert c.get(("b",), 2).known is True
    disabled = HotSerialCache(capacity=0)
    disabled.put(("x",), True, 1, 0.0)
    assert disabled.get(("x",), 1) is None and len(disabled) == 0


def test_oracle_cache_hit_and_epoch_invalidation(template):
    """Through the oracle: a miss fills the cache, the repeat hits it
    (same answer, no new batch), and once every replica refreshes past
    the cached epoch a formerly-absent serial reads known — the stale
    False cannot ghost across epochs."""
    agg = TpuAggregator(capacity=1 << 12, batch_size=64)
    agg.ingest([(syncerts.stamp_serial(template, j), template.issuer_der)
                for j in range(20)])
    issuer_id, eh = _identity(template)
    idx = agg.registry.index_of_issuer_id(issuer_id)
    oracle = MembershipOracle(agg, max_batch=64, max_delay_s=0.001,
                              max_staleness_s=1e9, replicas=2,
                              cache_size=128)
    try:
        present = (idx, eh, _serial_bytes(template, 3))
        ghost = (idx, eh, _serial_bytes(template, 999))
        r1 = oracle.query_raw([present, ghost])
        assert r1[0][0] is True and r1[1][0] is False
        assert len(oracle.cache) == 2
        batches_before = oracle.snapshots.stats()  # noqa: F841
        hits0, misses0 = oracle.cache.hits, oracle.cache.misses
        r2 = oracle.query_raw([present, ghost])
        assert oracle.cache.hits == hits0 + 2  # pure cache round
        assert oracle.cache.misses == misses0
        assert r2[0][0] is True and r2[1][0] is False
        assert r2[0][1] <= r1[0][1] + 1  # epoch surfaced, not invented
        # Ingest the ghost, then refresh EVERY replica past the cached
        # epoch: the stale False must be invalidated by construction.
        agg.ingest([(syncerts.stamp_serial(template, 999),
                     template.issuer_der)])
        for _ in range(oracle.snapshots.n_replicas):
            oracle.snapshots.refresh()
        r3 = oracle.query_raw([ghost])
        assert r3[0][0] is True, "stale cached False ghosted across epochs"
    finally:
        oracle.close()


# -- oversized-bulk split --------------------------------------------------


def test_bulk_split_oversized_submit_under_ingest(template):
    """A bulk larger than max_batch splits into max_batch-sized
    sub-requests (serve.split_requests), reassembled in order with
    exact parity — while ingest keeps feeding the table."""
    from ct_mapreduce_tpu.telemetry import metrics as tmetrics
    from ct_mapreduce_tpu.telemetry import trace

    agg = TpuAggregator(capacity=1 << 12, batch_size=64)
    agg.ingest([(syncerts.stamp_serial(template, j), template.issuer_der)
                for j in range(40)])
    issuer_id, eh = _identity(template)
    idx = agg.registry.index_of_issuer_id(issuer_id)
    sink = tmetrics.InMemSink()
    prev = tmetrics.get_sink()
    tmetrics.set_sink(sink)
    tracer = trace.enable()
    t0 = tracer.now_us()
    oracle = MembershipOracle(agg, max_batch=16, max_delay_s=0.001,
                              max_staleness_s=0.05, cache_size=-1)
    stop = threading.Event()

    def bg_ingest():
        j0 = 2000
        while not stop.is_set() and j0 < 2600:
            agg.ingest([(syncerts.stamp_serial(template, j),
                         template.issuer_der)
                        for j in range(j0, j0 + 64)])
            j0 += 64

    bg = threading.Thread(target=bg_ingest)
    bg.start()
    try:
        # 40 present + 20 absent = 60 lanes through a 16-lane cap.
        items = [(idx, eh, _serial_bytes(template, j)) for j in range(40)]
        items += [(idx, eh, _serial_bytes(template, j))
                  for j in range(5000, 5020)]
        for _ in range(3):
            res = oracle.query_raw(items)
            assert [r[0] for r in res] == [True] * 40 + [False] * 20
    finally:
        stop.set()
        bg.join()
        oracle.close()
        trace.disable()
        tmetrics.set_sink(prev)
    counters = sink.snapshot()["counters"]
    assert counters.get("serve.split_requests", 0) >= 3
    spans = [e for e in tracer.events()
             if e.get("ph") == "X" and e["name"] == "serve.batch"
             and e["ts"] >= t0]
    assert spans and all(e["args"]["lanes"] <= 16 for e in spans), \
        "an executed batch exceeded max_batch"


# -- staleness observability (refresh_in_flight / snapshot_age_s) ---------


def test_refresh_in_flight_and_age_surfaced(template, monkeypatch):
    from ct_mapreduce_tpu.serve import snapshot as snapmod
    from ct_mapreduce_tpu.telemetry import metrics as tmetrics

    agg = TpuAggregator(capacity=1 << 12, batch_size=64)
    sink = tmetrics.InMemSink()
    prev = tmetrics.get_sink()
    tmetrics.set_sink(sink)
    try:
        mgr = ReplicaPool(agg, n_replicas=1, max_staleness_s=1000.0)
        assert mgr.refresh_in_flight is False
        seen = {}
        orig = snapmod.capture_view

        def spying_capture(a, epoch, device=False):
            seen["in_flight"] = mgr.refresh_in_flight
            return orig(a, epoch, device=device)

        monkeypatch.setattr(snapmod, "capture_view", spying_capture)
        mgr.refresh()
        assert seen.pop("in_flight") is True  # flag held across the capture
        assert mgr.refresh_in_flight is False
        # ... and across a background swap of a stale pool.
        mgr.max_staleness_s = 0.0
        mgr.view()
        deadline = time.time() + 60
        while mgr.stats()["snapshot_epoch"] < 2 and time.time() < deadline:
            time.sleep(0.005)
        mgr.max_staleness_s = 1000.0
        monkeypatch.setattr(snapmod, "capture_view", orig)
        assert seen["in_flight"] is True
        st = mgr.stats()
        assert st["snapshot_epoch"] == 2 and st["snapshot_age_s"] >= 0
        assert st["replicas"] == 1
        mgr.view()
        gauges = sink.snapshot()["gauges"]
        assert "serve.snapshot_age_s" in gauges
        # A fuller pool surfaces the same observability per replica.
        pool = ReplicaPool(agg, n_replicas=2, max_staleness_s=1e9,
                           device=False).warm()
        pst = pool.stats()
        assert pst["refresh_in_flight"] is False
        assert pst["replicas"] == 2 and len(pst["replica_epochs"]) == 2
        assert pst["snapshot_age_s"] is not None
    finally:
        tmetrics.set_sink(prev)


def test_resolve_serve_layering(monkeypatch):
    """explicit > CTMR_SERVE_* env > defaults, unparseable ignored."""
    for k in ("CTMR_SERVE_REPLICAS", "CTMR_SERVE_DEVICE",
              "CTMR_SERVE_CACHE_SIZE"):
        monkeypatch.delenv(k, raising=False)
    assert resolve_serve() == (2, True, 4096)
    assert resolve_serve(replicas=5, device=False, cache_size=64) == \
        (5, False, 64)
    assert resolve_serve(cache_size=-1)[2] == 0  # -1 disables
    monkeypatch.setenv("CTMR_SERVE_REPLICAS", "7")
    monkeypatch.setenv("CTMR_SERVE_DEVICE", "0")
    monkeypatch.setenv("CTMR_SERVE_CACHE_SIZE", "99")
    assert resolve_serve() == (7, False, 99)
    assert resolve_serve(replicas=3, device=True, cache_size=16) == \
        (3, True, 16)  # explicit beats env
    monkeypatch.setenv("CTMR_SERVE_REPLICAS", "banana")
    assert resolve_serve()[0] == 2  # unparseable env ignored


# -- the snapshot. span family (ISSUE 33) -----------------------------------


def _traced_spans(tracer, t0):
    """The program's spans since ``t0``; the GIL probe's own (a thread
    ``trace.enable()`` starts, 100 a second) are not the test's."""
    return [e for e in tracer.events()
            if e.get("ph") == "X" and e["ts"] >= t0
            and e["name"] != "gil.probe"]


@pytest.mark.parametrize("device", [True, False],
                         ids=["device-views", "host-views"])
def test_capture_emits_the_snapshot_family(template, device):
    """One capture is one ``snapshot.capture`` under ``serve.snapshot``
    with its four children, each inside its parent's time; it says
    which replica it fills, through which folded entry it reads and how
    many table bytes crossed the host link: none for a device copy, the
    table's (one direction: read out) for a host mirror."""
    from ct_mapreduce_tpu.telemetry import metrics as tmetrics
    from ct_mapreduce_tpu.telemetry import trace

    agg = TpuAggregator(capacity=1 << 12, batch_size=64)
    agg.ingest([(syncerts.stamp_serial(template, j), template.issuer_der)
                for j in range(40)])
    table_bytes = int(np.asarray(agg.table.rows).nbytes)
    sink = tmetrics.InMemSink()
    prev = tmetrics.get_sink()
    tmetrics.set_sink(sink)
    tracer = trace.enable()
    t0 = tracer.now_us()
    try:
        pool = ReplicaPool(agg, n_replicas=2, device=device)
        view = pool.refresh()
        spans = _traced_spans(tracer, t0)
    finally:
        trace.disable()
        tmetrics.set_sink(prev)
    by_name = {e["name"]: e for e in spans}
    assert sorted(by_name) == [
        "serve.snapshot", "snapshot.capture", "snapshot.copy_dispatch",
        "snapshot.host_freeze", "snapshot.locked", "snapshot.wait_copy"]
    capture = by_name["snapshot.capture"]
    assert capture["parent"] == by_name["serve.snapshot"]["id"]
    assert by_name["snapshot.locked"]["parent"] == capture["id"]
    assert by_name["snapshot.wait_copy"]["parent"] == capture["id"]
    for inner in ("snapshot.copy_dispatch", "snapshot.host_freeze"):
        assert by_name[inner]["parent"] == by_name["snapshot.locked"]["id"]
    for e in spans:
        if e["parent"]:
            outer = next(p for p in spans if p["id"] == e["parent"])
            assert outer["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"]
    want_bytes = 0 if device else table_bytes
    assert capture["args"] == {
        "epoch": 1, "replica": 0, "through_entries": 40,
        "host_bytes": want_bytes, "shards": 1}
    assert view.replica_ix == 0 and view.through_entries == 40
    counters = sink.snapshot()["counters"]
    assert counters["snapshot.copies"] == 1
    assert counters["snapshot.host_bytes"] == want_bytes


def test_a_batch_names_the_capture_that_made_its_view(template):
    """``serve.batch`` and ``serve.lookup`` carry the ``epoch`` and
    ``age_ms`` of the view that answered, and that epoch is one a
    ``snapshot.capture`` finished before the batch began."""
    from ct_mapreduce_tpu.telemetry import trace

    agg = TpuAggregator(capacity=1 << 12, batch_size=64)
    agg.ingest([(syncerts.stamp_serial(template, j), template.issuer_der)
                for j in range(16)])
    issuer_id, eh = _identity(template)
    idx = agg.registry.index_of_issuer_id(issuer_id)
    tracer = trace.enable()
    t0 = tracer.now_us()
    try:
        oracle = MembershipOracle(agg, max_batch=64, max_delay_s=0.001,
                                  max_staleness_s=1e9, replicas=2,
                                  cache_size=0)
        try:
            oracle.snapshots.warm()
            for j in range(6):
                assert oracle.query_raw(
                    [(idx, eh, _serial_bytes(template, j))])[0][0] is True
        finally:
            oracle.close()
        spans = _traced_spans(tracer, t0)
    finally:
        trace.disable()
    captures = {e["args"]["epoch"]: e for e in spans
                if e["name"] == "snapshot.capture"}
    assert sorted(captures) == [1, 2]
    assert [captures[k]["args"]["replica"] for k in (1, 2)] == [0, 1]
    batches = [e for e in spans if e["name"] == "serve.batch"]
    lookups = {e["parent"]: e for e in spans if e["name"] == "serve.lookup"}
    assert len(batches) == 6
    assert {b["args"]["epoch"] for b in batches} == {1, 2}
    for b in batches:
        made = captures[b["args"]["epoch"]]
        assert made["ts"] + made["dur"] <= b["ts"]
        assert b["args"]["age_ms"] >= 0.0
        inner = lookups[b["id"]]["args"]
        assert (inner["epoch"], inner["age_ms"]) == (
            b["args"]["epoch"], b["args"]["age_ms"])
        assert inner["replica"] == made["args"]["replica"]


def test_the_copy_keeps_the_name_the_device_trace_is_matched_on():
    """``benchmark/readers/copy_roofline.py`` finds the copies by their
    XLA module, ``jit_snapshot_copy`` (docs/METRICS.md)."""
    import jax
    import jax.numpy as jnp

    from ct_mapreduce_tpu.serve.snapshot import snapshot_copy

    rows = jax.ShapeDtypeStruct((64, 8), jnp.uint32)
    assert "@jit_snapshot_copy" in snapshot_copy.lower(rows).as_text()


def test_a_burst_of_connections_is_queued_not_dropped(template):
    """100 clients connect in the same instant, as they do after any
    pause of the process or its machine (on the chip a 2.3 s freeze
    turned into 107 requests with no answer in 10 s: PERF.md, PR 33).
    Beyond the standard library's backlog of 5 the kernel drops them
    and they return in step, 1, 3 and 7 s later; with room to wait in,
    every one is answered at once."""
    import asyncio

    agg = TpuAggregator(capacity=1 << 12, batch_size=64)
    agg.ingest([(syncerts.stamp_serial(template, 1), template.issuer_der)])
    issuer_id, eh = _identity(template)
    srv = QueryServer(agg, 0, host="127.0.0.1").start()
    body = json.dumps({"issuer": issuer_id,
                       "expDate": ExpDate.from_unix_hour(eh).id(),
                       "serial": _serial_bytes(template, 1).hex()}).encode()
    request = (b"POST /query HTTP/1.1\r\nHost: x\r\nConnection: close\r\n"
               b"Content-Type: application/json\r\nContent-Length: %d\r\n\r\n"
               % len(body)) + body

    async def ask() -> tuple[float, bytes]:
        t0 = time.monotonic()
        reader, writer = await asyncio.open_connection("127.0.0.1", srv.port)
        writer.write(request)
        await writer.drain()
        raw = await reader.read(-1)
        writer.close()
        return time.monotonic() - t0, raw

    async def burst():
        return await asyncio.wait_for(
            asyncio.gather(*[ask() for _ in range(100)]), timeout=120)

    idx = agg.registry.index_of_issuer_id(issuer_id)
    try:
        srv.oracle.snapshots.warm()
        for lanes in (16, 32, 64, 128, 256):  # compile every batch width
            srv.oracle.query_raw(
                [(idx, eh, _serial_bytes(template, 1000 * lanes + j))
                 for j in range(lanes)])
        answers = asyncio.run(burst())
    finally:
        srv.stop()
    assert all(raw.startswith(b"HTTP/1.0 200") or raw.startswith(
        b"HTTP/1.1 200") for _s, raw in answers)
    assert all(b'"known": true' in raw for _s, raw in answers)
    # Dropped connections come back 1, 3, 7 and 15 s later, and by the
    # third wave some still collide; a burst that was queued is
    # answered in half a second on an idle machine.
    assert max(s for s, _raw in answers) < 6.5, sorted(
        s for s, _raw in answers)[-5:]


# -- the callback admission (PR 45) --------------------------------------


def test_batcher_admit_hands_results_to_its_callback():
    """``admit`` returns at once and parks nobody: the worker calls back
    once a request, whole requests are never split under ``max_batch``,
    and a bulk over it calls back once, its parts in order."""
    sizes = []

    def oracle(items):
        sizes.append(len(items))
        return [it * 2 for it in items]

    b = MicroBatcher(oracle, max_batch=4, max_delay_s=0.005)
    try:
        done = threading.Semaphore(0)
        calls = []

        def on_done(tag):
            calls.append((tag, threading.current_thread().name))
            done.release()

        before = threading.active_count()
        small = [b.admit([k, k + 100, k + 200], None, lambda k=k: on_done(k))
                 for k in range(3)]
        bulk = b.admit(list(range(10)), None, lambda: on_done("bulk"))
        assert threading.active_count() == before
        for _ in range(4):
            assert done.acquire(timeout=5)
        assert sorted(str(t) for t, _ in calls) == ["0", "1", "2", "bulk"]
        assert {name for _, name in calls} == {"serve-batcher"}
        for k, request in enumerate(small):
            assert request.results() == [2 * k, 2 * k + 200, 2 * k + 400]
        assert bulk.results() == [2 * k for k in range(10)]
        assert max(sizes) <= 4 and sum(sizes) == 19
        assert b.queue_lanes() == 0
    finally:
        b.close()


def test_batcher_admit_sheds_at_admission_and_expires_through_the_callback():
    """The guarantees hold whichever way a request came in: a full
    queue raises ``Overloaded`` from ``admit`` itself (no callback), a
    deadline passed in the queue reaches the callback as the request's
    error, and ``close`` lets the worker finish what is queued, calls
    back for it, and admits nothing more."""
    release = threading.Event()

    def oracle(items):
        release.wait(timeout=5)
        return items

    b = MicroBatcher(oracle, max_batch=2, max_delay_s=0.001,
                     max_queue_lanes=3)
    done = threading.Semaphore(0)
    try:
        first = b.admit([0], None, done.release)
        time.sleep(0.05)  # the worker is inside the oracle with [0]
        late = b.admit([1], 0.01, done.release)
        kept = b.admit([2, 3], None, done.release)
        with pytest.raises(Overloaded):
            b.admit([4], None, done.release)
        time.sleep(0.05)
        release.set()
        for _ in range(3):
            assert done.acquire(timeout=5)
        assert first.results() == [0] and kept.results() == [2, 3]
        with pytest.raises(DeadlineExceeded):
            late.results()
        assert not done.acquire(blocking=False)  # the shed one: no call
        release.clear()
        stuck = b.admit([5], None, done.release)
        time.sleep(0.05)
        queued = b.admit([6], None, done.release)
        threading.Timer(0.05, release.set).start()
        b.close()
        for _ in range(2):
            assert done.acquire(timeout=5)
        assert stuck.results() == [5] and queued.results() == [6]
        with pytest.raises(RuntimeError, match="closed"):
            b.admit([7], None, done.release)
    finally:
        release.set()
        b.close()


def test_oracle_admit_then_query_raw_does_not_wait(template):
    """``query_raw`` of what ``admit`` returned, after ``answered`` was
    called, is the blocking ``query_raw``'s answer without the wait, and
    fills the cache as it does; lanes the cache holds are not admitted
    again (``lanes`` 0, ``answered`` never called)."""
    agg = TpuAggregator(capacity=1 << 12, batch_size=64)
    agg.ingest([(syncerts.stamp_serial(template, j), template.issuer_der)
                for j in range(4)])
    issuer_id, eh = _identity(template)
    idx = agg.registry.index_of_issuer_id(issuer_id)
    items = [(idx, eh, _serial_bytes(template, j)) for j in (0, 3, 999)]
    oracle = MembershipOracle(agg, max_batch=64, max_delay_s=0.001)
    try:
        answered = threading.Event()
        asked = oracle.admit(items, None, answered.set)
        assert asked.lanes == 3
        assert answered.wait(timeout=10)
        t0 = time.monotonic()
        got = oracle.query_raw(asked)
        assert time.monotonic() - t0 < 0.5
        assert [r[0] for r in got] == [True, True, False]
        assert [r[0] for r in oracle.query_raw(items)] == [True, True, False]
        again = oracle.admit(items, None, lambda: pytest.fail("admitted"))
        assert again.lanes == 0 and oracle.cache.stats()["cache_hits"] >= 3
        assert oracle.query_raw(again) == again.out
    finally:
        oracle.close()


# -- one front thread (PR 45) ---------------------------------------------


def _front_server(template, n=8, **kwargs):
    agg = TpuAggregator(capacity=1 << 12, batch_size=64)
    agg.ingest([(syncerts.stamp_serial(template, j), template.issuer_der)
                for j in range(n)])
    issuer_id, eh = _identity(template)

    def query(j):
        return {"issuer": issuer_id,
                "expDate": ExpDate.from_unix_hour(eh).id(),
                "serial": _serial_bytes(template, j).hex()}

    kwargs.setdefault("max_delay_s", 0.001)
    return QueryServer(agg, 0, host="127.0.0.1", **kwargs), query


def _raw_request(doc, keep=False, path="/query"):
    body = json.dumps(doc).encode()
    return (f"POST {path} HTTP/1.1\r\nHost: x\r\n"
            f"Connection: {'keep-alive' if keep else 'close'}\r\n"
            "Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode() + body


def _read_response(sock):
    """One response off ``sock``: (status, headers, body)."""
    raw = b""
    while b"\r\n\r\n" not in raw:
        chunk = sock.recv(65536)
        if not chunk:
            raise ConnectionError(f"closed after {raw!r}")
        raw += chunk
    head, _, rest = raw.partition(b"\r\n\r\n")
    lines = head.decode().split("\r\n")
    headers = {k.lower(): v.strip() for k, _, v in
               (line.partition(":") for line in lines[1:])}
    want = int(headers["content-length"])
    while len(rest) < want:
        chunk = sock.recv(65536)
        if not chunk:
            raise ConnectionError("closed inside the body")
        rest += chunk
    assert len(rest) == want, "bytes past the answer"
    return int(lines[0].split()[1]), headers, rest


def _front_threads():
    return sorted(t.name for t in threading.enumerate()
                  if t.name.startswith(("query-", "Thread-")))


def test_front_answers_200_concurrent_connections_on_one_thread(template):
    """200 clients connect and ask at once; every one is answered, and
    the process has the one front thread it had before them: no thread
    a connection, and the pool's threads were never started."""
    import socket

    srv, query = _front_server(template)
    srv.start()
    try:
        before = _front_threads()
        assert before.count("query-front") == 1
        socks = [socket.create_connection(("127.0.0.1", srv.port), timeout=10)
                 for _ in range(200)]
        seen = set()
        for j, sock in enumerate(socks):
            sock.sendall(_raw_request(query(j % 16)))
            seen.update(_front_threads())
        answers = []
        for sock in socks:
            status, headers, body = _read_response(sock)
            seen.update(_front_threads())
            assert sock.recv(16) == b""  # closed after the answer
            sock.close()
            answers.append((status, json.loads(body)["known"]))
        assert answers == [(200, j % 16 < 8) for j in range(200)]
        assert sorted(seen) == before == _front_threads()
        assert not [n for n in before if n.startswith("query-pool")]
    finally:
        srv.stop()
    assert "query-front" not in _front_threads()


@pytest.mark.parametrize("how", ["split", "pipelined"])
def test_front_reads_a_request_in_pieces_and_two_at_once(template, how):
    """A request that arrives over several ``send``s is served once it
    is whole; two requests sent back to back on a kept connection are
    answered in order, and the connection stays open for a third."""
    import socket

    srv, query = _front_server(template)
    srv.start()
    try:
        sock = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
        if how == "split":
            raw = _raw_request(query(1))
            for cut in (raw[:7], raw[7:40], raw[40:-9], raw[-9:]):
                sock.sendall(cut)
                time.sleep(0.05)
            status, headers, body = _read_response(sock)
            assert status == 200 and json.loads(body)["known"] is True
            assert "connection" not in headers and sock.recv(16) == b""
        else:
            sock.sendall(_raw_request(query(2), keep=True)
                         + _raw_request(query(999), keep=True))
            first = _read_response(sock)
            second = _read_response(sock)
            assert [json.loads(r[2])["known"] for r in (first, second)] \
                == [True, False]
            assert first[1]["connection"] == "keep-alive"
            sock.sendall(_raw_request(query(3)))  # and now: close
            status, headers, body = _read_response(sock)
            assert json.loads(body)["known"] is True
            assert sock.recv(16) == b""
        sock.close()
    finally:
        srv.stop()


@pytest.mark.parametrize("what, code", [("head", 431), ("body", 413),
                                        ("line", 400), ("length", 400),
                                        ("method", 501), ("route", 404)])
def test_front_refuses_what_no_handler_should_see(template, what, code):
    """A header block or a body over the bound, a request line or a
    ``Content-Length`` that does not parse: a 4xx in JSON and the
    connection closed, without the bytes having been buffered; a method
    or a route the plane does not have: 501 / 404."""
    import socket

    from ct_mapreduce_tpu.serve.server import _Front

    srv, query = _front_server(template)
    srv.start()
    try:
        sock = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
        if what == "head":
            sock.sendall(b"POST /query HTTP/1.1\r\n")
            filler = b"X-Filler: " + b"a" * 1000 + b"\r\n"
            try:
                for _ in range(_Front.MAX_HEAD // len(filler) + 2):
                    sock.sendall(filler)
            except OSError:
                pass  # refused and closed under the sender
        elif what == "body":
            sock.sendall(b"POST /query HTTP/1.1\r\nContent-Length: %d\r\n\r\n"
                         % (_Front.MAX_BODY + 1))
        elif what == "line":
            sock.sendall(b"NONSENSE\r\n\r\n")
        elif what == "length":
            sock.sendall(b"POST /query HTTP/1.1\r\nContent-Length: -4\r\n\r\n")
        elif what == "method":
            sock.sendall(b"DELETE /query HTTP/1.1\r\n\r\n")
        else:
            sock.sendall(b"GET /nowhere HTTP/1.1\r\n"
                         b"Connection: keep-alive\r\n\r\n")
        status, headers, body = _read_response(sock)
        assert status == code and "error" in json.loads(body)
        assert headers["content-type"] == "application/json"
        if what == "route":  # a sound request: the connection is kept
            assert headers["connection"] == "keep-alive"
        else:
            try:
                assert sock.recv(16) == b""
            except ConnectionResetError:
                pass  # closed with the filler unread
        sock.close()
        # The plane answers the next client.
        code, body = _post(f"http://127.0.0.1:{srv.port}/query", query(1))
        assert code == 200 and body["known"] is True
    finally:
        srv.stop()


def test_front_drops_a_silent_client_at_the_deadline(template):
    """A client that connects and says nothing (or half a request) is
    dropped once no byte has moved for ``idle_s``; it holds no thread,
    and the clients beside it are answered meanwhile at their usual
    speed."""
    import socket

    srv, query = _front_server(template)
    srv.idle_s = 2.0
    srv.start()
    try:
        silent = socket.create_connection(("127.0.0.1", srv.port), timeout=20)
        half = socket.create_connection(("127.0.0.1", srv.port), timeout=20)
        half.sendall(_raw_request(query(1))[:30])
        t0 = time.monotonic()
        for j in range(10):
            code, body = _post(f"http://127.0.0.1:{srv.port}/query",
                               query(j % 8))
            assert code == 200 and body["known"] is True
        # Ten answers while the two are still connected: nobody waited
        # for their deadline.
        assert time.monotonic() - t0 < srv.idle_s
        for sock in (silent, half):
            assert sock.recv(16) == b""  # dropped, no answer
            sock.close()
        assert srv.idle_s <= time.monotonic() - t0 < 15.0
    finally:
        srv.stop()


@pytest.mark.parametrize("code", [429, 504, 500])
def test_front_answers_429_504_500_through_the_callback_path(template, code):
    """A full admission queue answers 429 ``overloaded`` at once, a
    deadline missed in the queue 504 ``deadline_exceeded`` when the
    batcher hands the request back, a batch that raised 500 with the
    exception's name; the loop is not held by any of them."""
    import socket

    srv, query = _front_server(
        template, cache_size=-1, max_queue_lanes=2 if code == 429 else 64)
    release = threading.Event()
    real = srv.oracle.batcher._run_batch

    def slow(items):
        release.wait(timeout=10)
        if code == 500:
            raise KeyError("the oracle broke")
        return real(items)

    srv.oracle.batcher._run_batch = slow
    srv.start()
    try:
        first = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
        first.sendall(_raw_request(query(0)))
        time.sleep(0.1)  # the batcher is inside the oracle with it
        second = socket.create_connection(("127.0.0.1", srv.port), timeout=10)
        if code == 429:
            second.sendall(_raw_request({"queries": [query(1)] * 3}))
        elif code == 504:
            second.sendall(_raw_request(dict(query(1), timeoutMs=20)))
        else:
            second.sendall(_raw_request(query(1)))
        if code == 429:  # answered while the batcher is still stuck
            status, _, body = _read_response(second)
            assert (status, json.loads(body)["error"]) == (429, "overloaded")
        # The loop still serves: a 404 needs no batch.
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/nowhere", timeout=5)
        assert ei.value.code == 404
        time.sleep(0.05)
        release.set()
        status, _, body = _read_response(first)
        if code == 500:
            assert status == 500
            assert json.loads(body)["error"].startswith("KeyError:")
        else:
            assert status == 200 and json.loads(body)["known"] is True
        if code != 429:
            status, _, body = _read_response(second)
            want = {504: "deadline_exceeded"}.get(code)
            assert status == code
            assert want is None or json.loads(body)["error"] == want
        first.close()
        second.close()
    finally:
        release.set()
        srv.stop()


def test_front_streams_a_filter_on_the_pool_while_queries_stay_flat(template):
    """A 48 MB ``/filter`` download to a client that reads slowly runs on
    a pool thread with a blocking socket of its own: ``/query`` latency
    beside it is what it was before it, the download arrives whole, and
    ``front.pool_requests`` counts it (and nothing else)."""
    import hashlib
    import socket

    from ct_mapreduce_tpu.telemetry import metrics as tmetrics

    srv, query = _front_server(template, cache_size=-1)
    blob = np.random.default_rng(45).bytes(48 << 20)
    routed = srv.handle_get

    def handle_get(path, qs, headers):
        if path == "/filter":
            return 200, blob, {"ETag": '"big"'}
        return routed(path, qs, headers)

    srv.handle_get = handle_get
    sink = tmetrics.InMemSink()
    prev = tmetrics.get_sink()
    tmetrics.set_sink(sink)
    srv.start()
    url = f"http://127.0.0.1:{srv.port}/query"

    def p95(n=60):
        took = []
        for j in range(n):
            t = time.monotonic()
            assert _post(url, query(j % 8))[0] == 200
            took.append(time.monotonic() - t)
        return sorted(took)[int(0.95 * n)]

    try:
        alone = p95()
        slow = socket.create_connection(("127.0.0.1", srv.port), timeout=30)
        slow.sendall(b"GET /filter HTTP/1.1\r\nHost: x\r\n\r\n")
        time.sleep(0.2)  # the pool thread has filled the socket's buffers
        assert "query-pool_0" in _front_threads()
        beside = p95()
        assert beside < 3 * alone + 0.05, (alone, beside)
        status, headers, body = _read_response(slow)
        assert status == 200 and headers["etag"] == '"big"'
        assert headers["content-type"] == "application/octet-stream"
        assert hashlib.sha256(body).digest() == hashlib.sha256(blob).digest()
        assert slow.recv(16) == b""
        slow.close()
        counters = sink.snapshot()["counters"]
        assert counters["front.requests"] == 121.0
        assert counters["front.pool_requests"] == 1.0
    finally:
        tmetrics.set_sink(prev)
        srv.stop()


def test_front_counts_every_request_and_the_pools_with_zero(template):
    """``front.requests`` and ``front.pool_requests`` are both added to
    on every request, the second with 0 where the loop answered itself:
    a reader tells "none left the loop" from "this program has no such
    counter"."""
    from ct_mapreduce_tpu.telemetry import metrics as tmetrics

    seen = []

    class Emitter(tmetrics.InMemSink):
        def incr_counter(self, key, value):
            if key.startswith("front."):
                seen.append((key, value))
            super().incr_counter(key, value)

    srv, query = _front_server(template)
    prev = tmetrics.get_sink()
    tmetrics.set_sink(Emitter())
    srv.start()
    try:
        base = f"http://127.0.0.1:{srv.port}"
        for j in range(3):
            assert _post(f"{base}/query", query(j))[0] == 200
        assert seen == [("front.requests", 1.0),
                        ("front.pool_requests", 0.0)] * 3
        with urllib.request.urlopen(f"{base}/healthz", timeout=10) as resp:
            assert json.loads(resp.read())["healthy"]
        assert seen[-2:] == [("front.requests", 1.0),
                             ("front.pool_requests", 1.0)]
    finally:
        tmetrics.set_sink(prev)
        srv.stop()


def test_front_loses_no_wake_up_under_a_short_switch_interval(template):
    """The batcher's thread and the pool's hand connections to the loop
    through a deque and one wake-up byte a drain: with the interpreter
    switching threads every 10 us and more clients than cores, on both
    kinds of route at once, every request is answered (a lost wake-up
    would leave one waiting until the next, or for ever)."""
    import sys

    srv, query = _front_server(template, cache_size=-1, max_delay_s=0.0)
    srv.start()
    base = f"http://127.0.0.1:{srv.port}"
    answered, errors = [], []

    def client(k):
        try:
            for j in range(25):
                if (j + k) % 5 == 0:
                    with urllib.request.urlopen(f"{base}/healthz",
                                                timeout=20) as resp:
                        answered.append(json.loads(resp.read())["healthy"])
                else:
                    code, body = _post(f"{base}/query", query((j + k) % 16),
                                       timeout=20)
                    answered.append(code == 200
                                    and body["known"] is ((j + k) % 16 < 8))
        except Exception as err:  # a timeout is the lost wake-up
            errors.append(err)

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        clients = [threading.Thread(target=client, args=(k,))
                   for k in range(24)]
        for t in clients:
            t.start()
        for t in clients:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in clients)
    finally:
        sys.setswitchinterval(before)
        srv.stop()
    assert not errors, errors
    assert len(answered) == 24 * 25 and all(answered)
