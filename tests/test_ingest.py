"""Ingest layer: leaf decode, client retry, sync engine end-to-end.

Mirrors the reference's ingest behaviors: RFC 6962 leaf handling
(ct-fetch.go:452), 429 backoff (ct-fetch.go:409-437), resume-from-
checkpoint (ct-fetch.go:288-305), tolerate-bad-entries
(ct-fetch.go:452-460), and the queue → worker store path
(ct-fetch.go:140-246).
"""

import datetime
import queue
import threading
import time

import pytest

from ct_mapreduce_tpu.core import der as hostder
from ct_mapreduce_tpu.core.types import CertificateLog, ExpDate, Issuer, Serial
from ct_mapreduce_tpu.ingest import (
    CTLogClient,
    LogSyncEngine,
    LogWorker,
    decode_entry,
    short_url,
)
from ct_mapreduce_tpu.ingest import leaf as leaflib
from ct_mapreduce_tpu.ingest.health import HealthServer
from ct_mapreduce_tpu.ingest.sync import AggregatorSink, DatabaseSink, polling_delay
from ct_mapreduce_tpu.storage.certdb import FilesystemDatabase
from ct_mapreduce_tpu.storage.mockbackend import MockBackend
from ct_mapreduce_tpu.storage.mockcache import MockRemoteCache

from tests import certgen
from tests.fakelog import FakeLog

UTC = datetime.timezone.utc
FUTURE = datetime.datetime(2031, 6, 15, tzinfo=UTC)


def _leaf_and_issuer(serial: int, issuer_cn: str = "Ingest CA"):
    issuer_der = certgen.make_cert(
        serial=1, issuer_cn=issuer_cn, is_ca=True, not_after=FUTURE
    )
    leaf_der = certgen.make_cert(
        serial=serial,
        issuer_cn=issuer_cn,
        subject_cn="leaf.example.com",
        is_ca=False,
        not_after=FUTURE,
    )
    return leaf_der, issuer_der


# -- leaf codec -------------------------------------------------------------


def test_leaf_roundtrip_x509():
    leaf_der, issuer_der = _leaf_and_issuer(7)
    li = leaflib.encode_leaf_input(leaf_der, timestamp_ms=1234)
    ed = leaflib.encode_extra_data([issuer_der])
    e = decode_entry(42, li, ed)
    assert e.index == 42
    assert e.timestamp_ms == 1234
    assert not e.is_precert
    assert e.cert_der == leaf_der
    assert e.issuer_der == issuer_der


def test_leaf_roundtrip_precert():
    leaf_der, issuer_der = _leaf_and_issuer(9)
    li = leaflib.encode_leaf_input(
        b"\x01" * 8, timestamp_ms=99, entry_type=leaflib.PRECERT_ENTRY,
        issuer_key_hash=b"\xab" * 32,
    )
    ed = leaflib.encode_extra_data(
        [issuer_der], entry_type=leaflib.PRECERT_ENTRY, pre_certificate=leaf_der
    )
    e = decode_entry(0, li, ed)
    assert e.is_precert
    # The stored cert is the SUBMITTED precert from extra_data
    # (ct-fetch.go:202-204), not the leaf_input TBS.
    assert e.cert_der == leaf_der
    assert e.issuer_key_hash == b"\xab" * 32
    assert e.issuer_der == issuer_der


def test_leaf_truncated_raises():
    with pytest.raises(leaflib.LeafDecodeError):
        leaflib.decode_leaf_input(b"\x00\x00\x01")


def test_short_url():
    assert short_url("https://ct.example.com/log/") == "ct.example.com/log"
    assert short_url("ct.example.com/log") == "ct.example.com/log"


# -- client -----------------------------------------------------------------


def test_client_sth_and_entries():
    log = FakeLog()
    leaf, issuer = _leaf_and_issuer(1)
    for s in range(5):
        log.add_cert(leaf, issuer, timestamp_ms=s)
    c = CTLogClient(log.url, transport=log.transport)
    sth = c.get_sth()
    assert sth.tree_size == 5
    entries = c.get_raw_entries(1, 3)
    assert [e.index for e in entries] == [1, 2, 3]


def test_client_429_backoff_and_retry_after():
    log = FakeLog()
    leaf, issuer = _leaf_and_issuer(2)
    log.add_cert(leaf, issuer)
    log.rate_limit_hits = 2
    log.retry_after = "3"
    sleeps = []
    c = CTLogClient(log.url, transport=log.transport, sleep=sleeps.append)
    sth = c.get_sth()
    assert sth.tree_size == 1
    assert sleeps == [3.0, 3.0]  # Retry-After honored, then success

    log.rate_limit_hits = 1
    log.retry_after = None
    sleeps.clear()
    c.get_sth()
    assert len(sleeps) == 1 and 0 < sleeps[0] <= 300.0  # jittered window


def test_client_5xx_backoff_same_lane():
    # Transient 5xx takes the exact 429 lane: backoff + jitter +
    # Retry-After clamp, then the same range retried.
    log = FakeLog()
    leaf, issuer = _leaf_and_issuer(2)
    log.add_cert(leaf, issuer)
    log.server_error_hits = 2
    log.server_error_status = 503
    log.retry_after = "7"
    sleeps = []
    c = CTLogClient(log.url, transport=log.transport, sleep=sleeps.append)
    sth = c.get_sth()
    assert sth.tree_size == 1
    assert sleeps == [7.0, 7.0]  # Retry-After honored on 5xx too

    log.server_error_hits = 1
    log.server_error_status = 502
    log.retry_after = None
    sleeps.clear()
    c.get_sth()
    assert len(sleeps) == 1 and 0 < sleeps[0] <= 300.0


def test_client_non_retryable_status_still_raises():
    from ct_mapreduce_tpu.ingest.ctclient import CTClientError

    log = FakeLog()
    leaf, issuer = _leaf_and_issuer(2)
    log.add_cert(leaf, issuer)
    sleeps = []
    c = CTLogClient(log.url, transport=log.transport, sleep=sleeps.append)
    with pytest.raises(CTClientError):
        c.get_raw_entries(5, 9)  # beyond tree size → 400: no retry
    assert sleeps == []


def test_client_window_clamps_to_served_page():
    log = FakeLog()
    leaf, issuer = _leaf_and_issuer(4)
    for s in range(10):
        log.add_cert(leaf, issuer, timestamp_ms=s)
    log.max_batch = 3  # the server's real cap, discovered on the wire
    c = CTLogClient(log.url, transport=log.transport)
    got = c.get_raw_entries(0, 9)
    assert [e.index for e in got] == [0, 1, 2]
    assert c.page_size == 3
    # The next window is pre-clamped: the wire shows end = start + 2.
    got = c.get_raw_entries(3, 9)
    assert [e.index for e in got] == [3, 4, 5]
    assert log.requests[-1].endswith("start=3&end=5")
    # A tail page shorter than the clamp (tree ends) must not shrink
    # the window further: 9..9 is a full answer for the asked range.
    got = c.get_raw_entries(9, 9)
    assert [e.index for e in got] == [9]
    assert c.page_size == 3


# -- LogWorker resume window ------------------------------------------------


def _db():
    return FilesystemDatabase(MockBackend(), MockRemoteCache())


def test_worker_resume_and_limit():
    log = FakeLog()
    leaf, issuer = _leaf_and_issuer(3)
    for s in range(10):
        log.add_cert(leaf, issuer)
    db = _db()
    state = CertificateLog(short_url="ct.example.com/fake", max_entry=4)
    db.save_log_state(state)

    c = CTLogClient(log.url, transport=log.transport)
    w = LogWorker(c, db)
    assert (w.start_pos, w.end_pos) == (4, 9)

    w2 = LogWorker(c, db, offset=7)
    assert w2.start_pos == 7
    w3 = LogWorker(c, db, limit=2)
    assert (w3.start_pos, w3.end_pos) == (4, 5)


# -- end-to-end sync: DatabaseSink ------------------------------------------


def test_sync_end_to_end_database_sink():
    log = FakeLog()
    issuer_der = certgen.make_cert(serial=1, issuer_cn="E2E CA", is_ca=True,
                                   not_after=FUTURE)
    serials = [100, 101, 102, 101, 100, 103]  # dupes dedup to 4
    for s in serials:
        leaf = certgen.make_cert(
            serial=s, issuer_cn="E2E CA", subject_cn="x.example.com",
            is_ca=False, not_after=FUTURE,
        )
        log.add_cert(leaf, issuer_der)
    log.add_garbage()  # tolerated, skipped (ct-fetch.go:452-460)
    ca_cert = certgen.make_cert(serial=200, issuer_cn="E2E CA", is_ca=True,
                                not_after=FUTURE)
    log.add_cert(ca_cert, issuer_der)  # filtered out: CA
    log.add_garbage()  # TRAILING garbage: cursor must still advance past it

    db = _db()
    sink = DatabaseSink(db, now=datetime.datetime(2025, 1, 1, tzinfo=UTC))
    engine = LogSyncEngine(sink, db, num_threads=2)
    engine.start_store_threads()
    engine.sync_log(log.url, transport=log.transport)
    engine.wait_for_downloads(timeout=30)
    engine.stop()

    issuer = Issuer.from_spki(certgen.spki_of(issuer_der))
    exp = ExpDate.from_time(hostder.parse_cert(issuer_der).not_after)
    known = db.get_known_certificates(exp, issuer)
    assert known.count() == 4
    for s in (100, 101, 102, 103):
        assert not known.was_unknown(Serial.from_der_cert(
            certgen.make_cert(serial=s, issuer_cn="E2E CA",
                              subject_cn="x.example.com", is_ca=False,
                              not_after=FUTURE)))
    # Checkpoint advanced to tree size — including past trailing
    # undecodable entries (tolerated skips are durable).
    st = db.get_log_state("ct.example.com/fake")
    assert st.max_entry == 9
    assert st.last_update_time is not None


def test_sync_stop_event_checkpoints():
    log = FakeLog()
    leaf, issuer = _leaf_and_issuer(5)
    for _ in range(30):
        log.add_cert(leaf, issuer)
    db = _db()
    sink = DatabaseSink(db, now=datetime.datetime(2025, 1, 1, tzinfo=UTC))
    engine = LogSyncEngine(sink, db, num_threads=1, limit=10)
    engine.start_store_threads()
    engine.sync_log(log.url, transport=log.transport)
    engine.wait_for_downloads(timeout=30)
    engine.stop()
    st = db.get_log_state("ct.example.com/fake")
    assert st.max_entry == 10  # limit clamp honored


# -- end-to-end sync: AggregatorSink (device path) --------------------------


def test_sync_end_to_end_aggregator_sink():
    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator

    log = FakeLog()
    issuer_der = certgen.make_cert(serial=1, issuer_cn="Agg CA", is_ca=True,
                                   not_after=FUTURE)
    for s in [500, 501, 500, 502]:
        leaf = certgen.make_cert(
            serial=s, issuer_cn="Agg CA", subject_cn="y.example.com",
            is_ca=False, not_after=FUTURE,
        )
        log.add_cert(leaf, issuer_der)

    agg = TpuAggregator(
        capacity=1 << 12, batch_size=64,
        now=datetime.datetime(2025, 1, 1, tzinfo=UTC),
    )
    db = _db()
    sink = AggregatorSink(agg, flush_size=3)
    engine = LogSyncEngine(sink, db, num_threads=1)
    engine.start_store_threads()
    engine.sync_log(log.url, transport=log.transport)
    engine.wait_for_downloads(timeout=60)
    engine.stop()

    snap = agg.drain()
    assert snap.total == 3  # 500, 501, 502
    assert sink.entries_in == 4


def test_sync_raw_batch_mode_matches_per_entry():
    """The native raw-batch fast path must produce the same aggregate
    state as the per-entry path, including garbage tolerance and
    checkpoint semantics."""
    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator

    def build_log():
        log = FakeLog()
        issuer_der = certgen.make_cert(serial=1, issuer_cn="Raw CA",
                                       is_ca=True, not_after=FUTURE)
        for s in [700, 701, 700, 702, 703, 701]:
            leaf = certgen.make_cert(
                serial=s, issuer_cn="Raw CA", subject_cn="r.example.com",
                is_ca=False, not_after=FUTURE,
            )
            log.add_cert(leaf, issuer_der, timestamp_ms=1700000000000 + s)
        log.add_garbage()
        ca = certgen.make_cert(serial=900, issuer_cn="Raw CA", is_ca=True,
                               not_after=FUTURE)
        log.add_cert(ca, issuer_der)
        return log

    results = []
    for raw in (False, True):
        log = build_log()
        agg = TpuAggregator(capacity=1 << 12, batch_size=64,
                            now=datetime.datetime(2025, 1, 1, tzinfo=UTC))
        db = _db()
        sink = AggregatorSink(agg, flush_size=4)
        engine = LogSyncEngine(sink, db, num_threads=2, raw_batches=raw)
        engine.start_store_threads()
        engine.sync_log(log.url, transport=log.transport)
        engine.wait_for_downloads(timeout=60)
        engine.stop()
        assert not engine.errors, engine.errors
        snap = agg.drain()
        st = db.get_log_state("ct.example.com/fake")
        results.append((snap.counts, snap.total, st.max_entry,
                        st.last_entry_time))
    assert results[0][:3] == results[1][:3]
    assert results[1][1] == 4  # 700,701,702,703
    assert results[1][2] == 8  # cursor past garbage + CA
    assert results[1][3] is not None  # timestamp recovered from prefix


def test_raw_batch_oversized_cert_host_lane():
    """A cert above the raw-path pad bucket takes the exact host lane
    and still lands in the aggregate."""
    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
    from ct_mapreduce_tpu.ingest.sync import RawBatch
    import base64

    from ct_mapreduce_tpu.ingest import leaf as leaflib

    issuer_der = certgen.make_cert(serial=1, issuer_cn="Big CA", is_ca=True,
                                   not_after=FUTURE)
    big = certgen.make_cert(
        serial=41, issuer_cn="Big CA", subject_cn="b.example.com",
        is_ca=False, not_after=FUTURE,
        crl_dps=tuple(f"http://crl{i}.big.example/{'p' * 60}.crl"
                      for i in range(12)),
    )
    small = certgen.make_cert(serial=42, issuer_cn="Big CA",
                              is_ca=False, not_after=FUTURE)
    assert len(small) <= 768 < len(big), (len(small), len(big))
    agg = TpuAggregator(capacity=1 << 12, batch_size=64,
                        now=datetime.datetime(2025, 1, 1, tzinfo=UTC))
    sink = AggregatorSink(agg, flush_size=64)
    sink.PAD_LEN = 768  # force the big cert over the bucket
    lis, eds = [], []
    for der in (big, small):
        lis.append(base64.b64encode(
            leaflib.encode_leaf_input(der, 1)).decode())
        eds.append(base64.b64encode(
            leaflib.encode_extra_data([issuer_der])).decode())
    sink.store_raw_batch(RawBatch(lis, eds, 0, "log"))
    sink.flush()
    assert agg.drain().total == 2


def test_device_queue_depth_pipelines_submissions():
    """SURVEY §2.2 PP row: at deviceQueueDepth >= 2 the sink SUBMITS
    batch N+1 before COMPLETING batch N — decode overlaps the device
    step, like the reference's downloader/worker channel overlap
    (ct-fetch.go:132,398-488). At depth 0 every dispatch completes
    synchronously. Both depths produce identical aggregate state."""
    import base64

    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
    from ct_mapreduce_tpu.ingest.sync import RawBatch

    issuer_der = certgen.make_cert(serial=1, issuer_cn="Pipe CA",
                                   is_ca=True, not_after=FUTURE)

    def raw_batch(serials):
        lis, eds = [], []
        for s in serials:
            der = certgen.make_cert(
                serial=s, issuer_cn="Pipe CA", subject_cn="p.example.com",
                is_ca=False, not_after=FUTURE,
            )
            lis.append(base64.b64encode(
                leaflib.encode_leaf_input(der, 1)).decode())
            eds.append(base64.b64encode(
                leaflib.encode_extra_data([issuer_der])).decode())
        return RawBatch(lis, eds, 0, "log")

    def run(depth):
        agg = TpuAggregator(capacity=1 << 12, batch_size=16,
                            now=datetime.datetime(2025, 1, 1, tzinfo=UTC))
        events = []
        submit_orig = agg.ingest_packed_submit

        def submit(*a, **k):
            p = submit_orig(*a, **k)
            events.append(("submit", id(p)))
            orig_complete = p.complete

            def complete():
                if not p._done:
                    events.append(("complete", id(p)))
                return orig_complete()

            p.complete = complete
            return p

        agg.ingest_packed_submit = submit
        sink = AggregatorSink(agg, flush_size=16, device_queue_depth=depth)
        for i in range(4):
            base = 1000 + 16 * i
            sink.store_raw_batch(raw_batch(range(base, base + 16)))
        sink.flush()
        snap = agg.drain()
        return events, snap

    ev0, snap0 = run(0)
    ev2, snap2 = run(2)
    assert snap0.counts == snap2.counts
    assert snap0.total == snap2.total == 64
    kinds0 = [k for k, _ in ev0]
    assert kinds0 == ["submit", "complete"] * 4  # depth 0: fully serial
    kinds2 = [k for k, _ in ev2]
    # depth 2: three submissions are in flight before the first readback.
    assert kinds2.index("complete") == 3
    # FIFO: completion order equals submission order.
    sub_ids = [i for k, i in ev2 if k == "submit"]
    com_ids = [i for k, i in ev2 if k == "complete"]
    assert com_ids == sub_ids


def test_raw_batch_row_narrowing():
    """When every cert fits half the pad, the sink ships the narrow row
    view (H2D bytes halve) and results are identical."""
    import base64

    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
    from ct_mapreduce_tpu.ingest.sync import RawBatch

    issuer_der = certgen.make_cert(serial=1, issuer_cn="Narrow CA",
                                   is_ca=True, not_after=FUTURE)
    lis, eds = [], []
    for s in (61, 62, 63):
        der = certgen.make_cert(serial=s, issuer_cn="Narrow CA",
                                is_ca=False, not_after=FUTURE)
        assert len(der) <= 1024
        lis.append(base64.b64encode(leaflib.encode_leaf_input(der, 1)).decode())
        eds.append(base64.b64encode(
            leaflib.encode_extra_data([issuer_der])).decode())

    agg = TpuAggregator(capacity=1 << 12, batch_size=16,
                        now=datetime.datetime(2025, 1, 1, tzinfo=UTC))
    seen_widths = []
    orig = agg.ingest_packed_submit

    def spy(data, *a, **kw):
        seen_widths.append(data.shape[1])
        return orig(data, *a, **kw)

    agg.ingest_packed_submit = spy
    sink = AggregatorSink(agg, flush_size=16)
    sink.store_raw_batch(RawBatch(lis, eds, 0, "log"))
    sink.flush()
    assert seen_widths == [sink.PAD_LEN // 2]  # narrow view shipped
    assert agg.drain().total == 3


# -- health -----------------------------------------------------------------


class _FakeEngine:
    def __init__(self):
        self.updates = {}

    def last_updates(self):
        return dict(self.updates)


def test_health_transitions():
    eng = _FakeEngine()
    h = HealthServer(eng, polling_delay_mean_s=10.0, addr="127.0.0.1:0")
    code, body = h.status()
    assert code == 503  # before first update (ct-fetch.go:584-588)
    eng.updates["log"] = datetime.datetime.now(UTC)
    code, body = h.status()
    assert code == 200 and body["status"] == "ok"
    eng.updates["log"] = datetime.datetime.now(UTC) - datetime.timedelta(seconds=25)
    code, body = h.status()
    assert code == 500 and "log" in body["stalled"]


def test_health_http_server():
    import urllib.request

    eng = _FakeEngine()
    eng.updates["log"] = datetime.datetime.now(UTC)
    h = HealthServer(eng, polling_delay_mean_s=10.0, addr="127.0.0.1:0")
    h.start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{h.port}/health", timeout=5
        ) as resp:
            assert resp.status == 200
    finally:
        h.stop()


def test_polling_delay_positive():
    for _ in range(100):
        assert polling_delay(600.0, 10) >= 1.0


def test_cursor_saved_on_download_error():
    """A transport failure mid-range must still run the exit state save
    (reference saves on error paths too, ct-fetch.go:367): progress up
    to the failure survives; re-fetch of the failed range is dedup-safe."""
    from ct_mapreduce_tpu.ingest.ctclient import CTClientError

    log = FakeLog()
    leaf, issuer = _leaf_and_issuer(5)
    for _ in range(6):
        log.add_cert(leaf, issuer)
    log.max_batch = 2  # 3 get-entries requests for the full range

    calls = {"n": 0}

    def failing_transport(url):
        if "get-entries" in url:
            calls["n"] += 1
            if calls["n"] >= 2:
                return 500, {}, b"transport down"
        return log.transport(url)

    db = _db()
    # A 500 takes the retry lane (jittered 0.5 s–5 min, 100 tries): no
    # real sleeps, and a budget small enough to give up inside the test.
    c = CTLogClient(log.url, transport=failing_transport,
                    sleep=lambda _s: None, max_retries=3)
    w = LogWorker(c, db)
    q = queue.Queue()
    with pytest.raises(CTClientError):
        w.run(q, threading.Event(), save_period_s=1e9)
    st = db.get_log_state("ct.example.com/fake")
    assert st.max_entry == 2  # first batch durable, not lost


def test_sync_multi_log_shared_sink():
    """BASELINE config #5's shape: several logs, one downloader thread
    each (the reference's per-log goroutines, ct-fetch.go:527-565),
    all feeding ONE shared aggregator. Dedup spans logs (the identity
    is (expDate, issuer, serial), not the log), and each log keeps an
    independent resumable cursor."""
    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator

    issuer_der = certgen.make_cert(serial=1, issuer_cn="Multi CA",
                                   is_ca=True, not_after=FUTURE)

    def leaf(s):
        return certgen.make_cert(
            serial=s, issuer_cn="Multi CA", subject_cn="m.example.com",
            is_ca=False, not_after=FUTURE,
        )

    log_a = FakeLog(url="https://ct.example.com/a")
    log_b = FakeLog(url="https://ct.example.com/b")
    for s in (700, 701, 702):
        log_a.add_cert(leaf(s), issuer_der)
    # b overlaps a on 701/702 — cross-log duplicates must dedup.
    for s in (701, 702, 703, 704):
        log_b.add_cert(leaf(s), issuer_der)

    agg = TpuAggregator(
        capacity=1 << 12, batch_size=64,
        now=datetime.datetime(2025, 1, 1, tzinfo=UTC),
    )
    db = _db()
    sink = AggregatorSink(agg, flush_size=3)
    engine = LogSyncEngine(sink, db, num_threads=2)
    engine.start_store_threads()
    engine.sync_log(log_a.url, transport=log_a.transport)
    engine.sync_log(log_b.url, transport=log_b.transport)
    engine.wait_for_downloads(timeout=60)
    engine.stop()

    snap = agg.drain()
    assert snap.total == 5  # 700..704 exactly once across both logs
    assert sink.entries_in == 7
    # Independent per-log cursors at each tree size.
    assert db.get_log_state("ct.example.com/a").max_entry == 3
    assert db.get_log_state("ct.example.com/b").max_entry == 4


def test_sync_contention_stress_exact_totals():
    """The -race-tier analog (the reference runs `go test -race`,
    .travis.yml:13): four logs with overlapping serials, four store
    workers, a deliberately ragged flush size and pipelining depth 3 —
    any lost/duplicated dispatch under contention breaks the exact
    totals, which are asserted to the entry."""
    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator

    issuer_der = certgen.make_cert(serial=1, issuer_cn="Race CA",
                                   is_ca=True, not_after=FUTURE)

    def leaf(s):
        return certgen.make_cert(
            serial=s, issuer_cn="Race CA", subject_cn="r.example.com",
            is_ca=False, not_after=FUTURE,
        )

    logs = []
    unique = set()
    for k in range(4):
        log = FakeLog(url=f"https://ct.example.com/race{k}")
        # Serial windows overlap between neighboring logs.
        for s in range(900 + 10 * k, 900 + 10 * k + 17):
            log.add_cert(leaf(s), issuer_der)
            unique.add(s)
        logs.append(log)

    agg = TpuAggregator(
        capacity=1 << 12, batch_size=32,
        now=datetime.datetime(2025, 1, 1, tzinfo=UTC),
    )
    db = _db()
    sink = AggregatorSink(agg, flush_size=7, device_queue_depth=3)
    engine = LogSyncEngine(sink, db, num_threads=4)
    engine.start_store_threads()
    for log in logs:
        engine.sync_log(log.url, transport=log.transport)
    engine.wait_for_downloads(timeout=90)
    engine.stop()

    snap = agg.drain()
    assert snap.total == len(unique), (snap.total, len(unique))
    assert sink.entries_in == 4 * 17
    for k in range(4):
        st = db.get_log_state(f"ct.example.com/race{k}")
        assert st.max_entry == 17


def test_raw_batch_narrow_decode_and_redecode():
    """The raw path picks the narrow row width BEFORE decoding when
    every leaf_input provably fits (base64 length bound), and
    redecodes at full width when a precert-style entry turns out
    TOO_LONG for the narrow rows — counts exact either way."""
    import base64

    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
    from ct_mapreduce_tpu.ingest import leaf as leaflib
    from ct_mapreduce_tpu.ingest.sync import RawBatch
    from ct_mapreduce_tpu.native import leafpack

    issuer_der = certgen.make_cert(serial=1, issuer_cn="Nar CA",
                                   is_ca=True, not_after=FUTURE)
    small = [certgen.make_cert(serial=50 + i, issuer_cn="Nar CA",
                               subject_cn=f"n{i}.example.com",
                               is_ca=False, not_after=FUTURE)
             for i in range(4)]
    ed = base64.b64encode(leaflib.encode_extra_data([issuer_der])).decode()

    pads_seen = []
    orig = leafpack.decode_raw_pages

    def spy(pages, pad_len, workers=None, threads=None):
        pads_seen.append(pad_len)
        return orig(pages, pad_len, workers=workers, threads=threads)


    # (a) all-small batch: ONE decode at the narrow width.
    agg = TpuAggregator(capacity=1 << 12, batch_size=64,
                        now=datetime.datetime(2025, 1, 1, tzinfo=UTC))
    sink = AggregatorSink(agg, flush_size=64)
    lis = [base64.b64encode(
        leaflib.encode_leaf_input(der, i)).decode()
        for i, der in enumerate(small)]
    leafpack.decode_raw_pages = spy
    try:
        sink.store_raw_batch(RawBatch(lis, [ed] * len(lis), 0, "log"))
        sink.flush()
        assert pads_seen == [sink.PAD_LEN // 2]
        assert agg.drain().total == len(small)

        # (b) a precert whose cert rides in extra_data and exceeds the
        # narrow width: leaf_input stays tiny (the bound can't see it),
        # the narrow decode flags TOO_LONG, and ONE full-width
        # redecode lands everything exactly.
        pads_seen.clear()
        big = certgen.make_cert(
            serial=77, issuer_cn="Nar CA", subject_cn="pc.example.com",
            is_ca=False, not_after=FUTURE,
            extra_extensions=30, extra_ext_size=40)
        assert sink.PAD_LEN // 2 < len(big) <= sink.PAD_LEN, len(big)
        pre_li = base64.b64encode(leaflib.encode_leaf_input(
            b"\x00" * 10, 7,
            entry_type=leaflib.PRECERT_ENTRY)).decode()
        pre_ed = base64.b64encode(leaflib.encode_extra_data(
            [issuer_der], entry_type=leaflib.PRECERT_ENTRY,
            pre_certificate=big)).decode()
        agg2 = TpuAggregator(capacity=1 << 12, batch_size=64,
                             now=datetime.datetime(2025, 1, 1, tzinfo=UTC))
        sink2 = AggregatorSink(agg2, flush_size=64)
        sink2.store_raw_batch(RawBatch(
            lis + [pre_li], [ed] * len(lis) + [pre_ed], 0, "log"))
        sink2.flush()
        assert pads_seen == [sink2.PAD_LEN // 2, sink2.PAD_LEN]
        assert agg2.drain().total == len(small) + 1
    finally:
        leafpack.decode_raw_pages = orig


def test_oversized_issuer_gets_own_status_no_redecode():
    """ADVICE r05: a >=2 MiB issuer DER used to come back as TOO_LONG,
    so any batch containing one paid a futile full-width redecode of
    the whole batch. It now gets ISSUER_TOO_LONG — no redecode — and
    the entry still lands via the exact per-entry host lane."""
    import base64

    import numpy as np

    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
    from ct_mapreduce_tpu.ingest.sync import RawBatch
    from ct_mapreduce_tpu.native import leafpack

    # A real-signed certificate inflated past the 2 MiB span-packing
    # bound with one opaque private-arc extension.
    huge_issuer = certgen.make_cert(
        serial=1, issuer_cn="Huge CA", is_ca=True, not_after=FUTURE,
        extra_extensions=1, extra_ext_size=(1 << 21) + 256,
    )
    assert len(huge_issuer) >= (1 << 21)
    normal_issuer = certgen.make_cert(serial=1, issuer_cn="Ovs CA",
                                      is_ca=True, not_after=FUTURE)
    small = [certgen.make_cert(serial=80 + i, issuer_cn="Ovs CA",
                               subject_cn="o.example.com", is_ca=False,
                               not_after=FUTURE) for i in range(3)]
    victim = certgen.make_cert(serial=99, issuer_cn="Huge CA",
                               subject_cn="h.example.com", is_ca=False,
                               not_after=FUTURE)
    lis = [base64.b64encode(leaflib.encode_leaf_input(d, i)).decode()
           for i, d in enumerate(small + [victim])]
    eds = ([base64.b64encode(
        leaflib.encode_extra_data([normal_issuer])).decode()] * len(small)
        + [base64.b64encode(
            leaflib.encode_extra_data([huge_issuer])).decode()])

    dec = leafpack.decode_raw_batch(lis, eds, 2048)
    assert dec.status[-1] == leafpack.ISSUER_TOO_LONG
    assert dec.length[-1] == len(victim)  # cert row packed fine
    np.testing.assert_array_equal(
        dec.status, leafpack._decode_python(lis, eds, 2048).status)

    pads_seen = []
    orig = leafpack.decode_raw_pages

    def spy(pages, pad_len, workers=None, threads=None):
        pads_seen.append(pad_len)
        return orig(pages, pad_len, workers=workers, threads=threads)

    agg = TpuAggregator(capacity=1 << 12, batch_size=64,
                        now=datetime.datetime(2025, 1, 1, tzinfo=UTC))
    sink = AggregatorSink(agg, flush_size=64)
    leafpack.decode_raw_pages = spy
    try:
        sink.store_raw_batch(RawBatch(lis, eds, 0, "log"))
        sink.flush()
    finally:
        leafpack.decode_raw_pages = orig
    # Narrow pre-decode, ONE decode — the overloaded status used to
    # force [narrow, full] here.
    assert pads_seen == [sink.PAD_LEN // 2], pads_seen
    assert agg.drain().total == len(small) + 1


# -- the one dispatch path: decode -> put -> submit -> fold -----------------
#
# Fixtures from ``ct_mapreduce_tpu.utils.minicert`` (hand-assembled
# canonical DER): the ingest path parses and never verifies, so
# synthetic signature bytes are within contract.

MINI_NOW = datetime.datetime(2025, 1, 1, tzinfo=UTC)


def _mini_issuers():
    from ct_mapreduce_tpu.utils import minicert

    return [minicert.make_cert(serial=1, issuer_cn=f"Mini CA {k}", is_ca=True)
            for k in range(2)]


def _mini_batch(start: int, n: int):
    """n wire entries alternating two issuers, serials start..start+n."""
    import base64

    from ct_mapreduce_tpu.ingest.sync import RawBatch
    from ct_mapreduce_tpu.utils import minicert

    issuers = _mini_issuers()
    lis, eds = [], []
    for j in range(n):
        k = j % 2
        leaf = minicert.make_cert(
            serial=start + j, issuer_cn=f"Mini CA {k}",
            subject_cn="mini.example", is_ca=False,
        )
        lis.append(base64.b64encode(
            leaflib.encode_leaf_input(leaf, 1000 + start + j)).decode())
        eds.append(base64.b64encode(
            leaflib.encode_extra_data([issuers[k]])).decode())
    return RawBatch(lis, eds, start, "mini-log")


def _mini_sink(depth: int = 2, flush_size: int = 32):
    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator

    agg = TpuAggregator(capacity=1 << 12, batch_size=flush_size,
                        now=MINI_NOW)
    return agg, AggregatorSink(agg, flush_size=flush_size,
                               device_queue_depth=depth)


def test_issuer_too_long_status_skips_futile_redecode():
    """Satellite (ADVICE r05): a >=2 MiB issuer DER gets its own
    status (ISSUER_TOO_LONG) — the cert itself packed fine, so the
    batch must NOT pay a full-width redecode that cannot clear it —
    and the entry still lands via the exact host lane."""
    import base64

    import numpy as np

    from ct_mapreduce_tpu.ingest.sync import RawBatch
    from ct_mapreduce_tpu.native import leafpack
    from ct_mapreduce_tpu.utils import minicert

    huge_issuer = minicert.make_cert(
        serial=1, issuer_cn="Huge CA", is_ca=True,
        extra_ext_bytes=(1 << 21) + 256,
    )
    assert len(huge_issuer) >= (1 << 21)
    small = [minicert.make_cert(serial=50 + i, issuer_cn="Mini CA 0",
                                subject_cn="s.example", is_ca=False)
             for i in range(3)]
    victim = minicert.make_cert(serial=99, issuer_cn="Huge CA",
                                subject_cn="v.example", is_ca=False)

    lis = [base64.b64encode(leaflib.encode_leaf_input(d, i)).decode()
           for i, d in enumerate(small + [victim])]
    eds = ([base64.b64encode(
        leaflib.encode_extra_data([_mini_issuers()[0]])).decode()]
        * len(small)
        + [base64.b64encode(
            leaflib.encode_extra_data([huge_issuer])).decode()])

    # Decoder level: dedicated status on BOTH lanes of the fallback
    # matrix (native when a compiler exists, pure Python always).
    dec_py = leafpack._decode_python(lis, eds, 2048)
    assert dec_py.status[-1] == leafpack.ISSUER_TOO_LONG
    assert dec_py.length[-1] == len(victim)  # the cert row IS packed
    from ct_mapreduce_tpu.native import available
    if available():
        dec_nat = leafpack.decode_raw_batch(lis, eds, 2048)
        np.testing.assert_array_equal(dec_nat.status, dec_py.status)

    # Sink level: the narrow pre-decode stays a SINGLE decode (the old
    # overloaded TOO_LONG forced a futile full-width redecode here).
    pads_seen = []
    orig = leafpack.decode_raw_pages

    def spy(pages, pad_len, workers=None, threads=None):
        pads_seen.append(pad_len)
        return orig(pages, pad_len, workers=workers, threads=threads)

    agg, sink = _mini_sink(flush_size=64)
    leafpack.decode_raw_pages = spy
    try:
        sink.store_raw_batch(RawBatch(lis, eds, 0, "log"))
        sink.flush()
    finally:
        leafpack.decode_raw_pages = orig
    assert pads_seen == [sink.PAD_LEN // 2], pads_seen
    # ... and the oversized-issuer entry still counted, exactly once.
    assert agg.drain().total == len(small) + 1


def test_lock_wait_sampled_outside_store_envelope():
    """dispatchLockWait is its own sample and the storeCertificate
    envelope opens only after the lock is held — a submit budget
    must not fold lock contention into submit cost."""
    from ct_mapreduce_tpu.telemetry import metrics as tmetrics

    sink_metrics = tmetrics.InMemSink()
    prev = tmetrics.get_sink()
    tmetrics.set_sink(sink_metrics)
    try:
        agg, sink = _mini_sink(depth=2)
        for i in range(4):
            sink.store_raw_batch(_mini_batch(i * 32, 32))
        sink.close()
    finally:
        tmetrics.set_sink(prev)
    samples = sink_metrics.snapshot()["samples"]
    assert "ct-fetch.dispatchLockWait" in samples
    assert "ct-fetch.storeCertificate" in samples
    # One lock sample per submitted chunk (4 chunks + the flush
    # barrier), all non-negative.
    assert samples["ct-fetch.dispatchLockWait"]["count"] >= 4
    assert samples["ct-fetch.dispatchLockWait"]["min"] >= 0.0


def _fail_second_call(obj, name: str, boom: Exception) -> None:
    """Patch ``obj.name`` so that its second call raises ``boom``."""
    orig = getattr(obj, name)
    calls = {"n": 0}

    def failing(*args, **kwargs):
        calls["n"] += 1
        if calls["n"] == 2:
            raise boom
        return orig(*args, **kwargs)

    setattr(obj, name, failing)


def _flush_within(sink, seconds: float) -> None:
    done = threading.Event()
    errs = []

    def run():
        try:
            sink.flush()
        except Exception as err:  # reported by the assert below
            errs.append(err)
        done.set()

    threading.Thread(target=run, daemon=True).start()
    assert done.wait(seconds), \
        "flush() waits for a cut whose dispatch never released it"
    assert not errs, errs


# stage -> (what to patch, entries the aggregate holds at the end of
# the four batches the test feeds). A batch that died in decode or
# before its submit never reached the device; a batch whose fold was
# refused before it began stays outstanding and the report's drain
# folds it.
_FAILURES = {
    "decode": (lambda agg, sink: (sink, "_prepare_chunk"), 3 * 32),
    "submit": (lambda agg, sink: (agg, "ingest_packed_submit"), 3 * 32),
    "fold": (lambda agg, sink: (sink, "_complete_item"), 4 * 32),
}


@pytest.mark.parametrize("stage", sorted(_FAILURES))
def test_a_dispatch_that_raises_releases_its_cut(stage):
    """Decode, submit or fold raises under ``store_raw_batch``: the
    exception reaches the caller as it is, the cut it was dispatching
    is handed over all the same (``_handing_over``), so a later
    ``flush()`` does not wait for it for ever, the sink goes on
    taking batches, and ``close()`` works."""
    agg, sink = _mini_sink(depth=1)
    boom = RuntimeError(f"{stage} exploded")
    where, total = _FAILURES[stage]
    _fail_second_call(*where(agg, sink), boom)
    fed = 0
    with pytest.raises(RuntimeError) as exc_info:
        while fed < 3:
            fed += 1
            sink.store_raw_batch(_mini_batch(fed * 32, 32))
    assert exc_info.value is boom
    assert sink._open_cuts == set()
    _flush_within(sink, 60.0)
    while fed < 4:
        fed += 1
        sink.store_raw_batch(_mini_batch(fed * 32, 32))
    sink.close()
    assert sink._open_cuts == set() and not sink._inflight
    assert agg.drain().total == total


def test_the_batches_behind_a_failed_fold_are_still_folded_exactly():
    """A fold raises with later batches already submitted behind it
    (deviceQueueDepth 2): the next barrier folds those, in order, and
    every count is exact for all that reached the device."""
    import numpy as np

    agg, sink = _mini_sink(depth=2)
    folded = []
    orig = sink._complete_item

    def complete(pending, der_of):
        if pending.batch == 2:
            raise RuntimeError("fold of batch 2 exploded")
        folded.append(pending.batch)
        orig(pending, der_of)

    sink._complete_item = complete
    with pytest.raises(RuntimeError, match="batch 2"):
        for i in range(5):
            sink.store_raw_batch(_mini_batch(i * 32, 32))
    # Batch 2's fold was due when batch 4 was submitted; 3 and 4 wait.
    assert folded == [1]
    assert [p.batch for p, _ in sink._inflight] == [3, 4]
    sink.flush()
    assert folded == [1, 3, 4] and not sink._inflight
    snap = agg.drain()  # folds the refused batch 2, still outstanding
    assert snap.total == 4 * 32
    assert int(np.asarray(agg.table.count)) == 4 * 32
    assert agg.metrics["inserted"] == 4 * 32 and agg.metrics["known"] == 0
    assert sorted(snap.counts.values()) == [2 * 32, 2 * 32]


def test_a_per_entry_dispatch_that_raises_releases_its_cut():
    """The same for ``store()``, the per-entry lane: the batch it cut
    fails in ``_dispatch`` and the cut is handed over all the same."""
    from ct_mapreduce_tpu.ingest.leaf import DecodedEntry
    from ct_mapreduce_tpu.utils import minicert

    agg, sink = _mini_sink(flush_size=4)
    issuer = _mini_issuers()[0]
    boom = RuntimeError("ingest exploded")
    _fail_second_call(agg, "ingest", boom)

    def entry(i):
        leaf = minicert.make_cert(serial=500 + i, issuer_cn="Mini CA 0",
                                  subject_cn="e.example", is_ca=False)
        return DecodedEntry(index=i, timestamp_ms=1,
                            entry_type=leaflib.X509_ENTRY,
                            cert_der=leaf, issuer_der=issuer)

    with pytest.raises(RuntimeError) as exc_info:
        for i in range(8):
            sink.store(entry(i), "mini-log")
    assert exc_info.value is boom
    assert sink._open_cuts == set()
    _flush_within(sink, 60.0)
    for i in range(8, 12):
        sink.store(entry(i), "mini-log")
    sink.close()
    assert agg.drain().total == 8  # the second batch of four was lost


def test_flush_waits_for_the_cuts_before_it_and_for_no_later_one():
    """``flush()`` is the barrier a cursor save stands on: a cut that a
    store thread took before it and is still decoding is in neither
    accumulator nor in flight, so the flush waits until that cut is
    handed over; a cut taken after the flush looked is not its to wait
    for."""
    agg, sink = _mini_sink(depth=2)
    release = {1: threading.Event(), 2: threading.Event()}
    entered = {1: threading.Event(), 2: threading.Event()}
    orig = sink._prepare_chunk

    def held_prepare(chunk):
        entered[chunk.batch].set()
        assert release[chunk.batch].wait(60)
        return orig(chunk)

    sink._prepare_chunk = held_prepare
    stores = [threading.Thread(
        target=sink.store_raw_batch, args=(_mini_batch(i * 32, 32),),
        daemon=True) for i in range(2)]
    stores[0].start()
    assert entered[1].wait(60)
    flushed = threading.Event()
    flusher = threading.Thread(
        target=lambda: (sink.flush(), flushed.set()), daemon=True)
    flusher.start()
    # The flush has taken its own (empty) cut once the sink's cut
    # counter has passed the store's: only then is batch 2 a later one.
    deadline = time.monotonic() + 60
    while sink._cut_seq < 2 and time.monotonic() < deadline:
        time.sleep(0.005)
    assert sink._cut_seq == 2
    stores[1].start()
    assert entered[2].wait(60)
    assert not flushed.wait(0.3), "flush() passed a cut still decoding"
    release[1].set()
    assert flushed.wait(60), "flush() waits for a cut taken after it"
    assert agg.metrics["inserted"] == 32  # batch 1 folded by the barrier
    release[2].set()
    for t in stores + [flusher]:
        t.join(60)
    sink.close()
    assert agg.drain().total == 64
