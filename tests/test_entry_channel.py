"""The entry channel of ``LogSyncEngine`` (ingest/sync.py): bounded by
the ENTRIES it holds whatever the size of its items; one batch of the
sink it feeds where whole get-entries responses go to a sink that
pauses for a batch, the reference's 16,384 where the sink takes an
entry at a time. The guarantees
stay what they were: a cursor never passes an entry that has not been
through the sink, ``stop()`` drains, and a page that never reached a
worker is fetched again.

The log here serves one tiny entry over and over (a log of 70,000 is
5 MB): the sinks count and never decode.
"""

import base64
import json
import queue
import threading
import time
from urllib.parse import parse_qs, urlparse

import pytest

from ct_mapreduce_tpu.ingest import leaf as leaflib
from ct_mapreduce_tpu.ingest.sync import (
    ENTRY_QUEUE_CAPACITY,
    LogSyncEngine,
    RawBatch,
    _EntryChannel,
)
from ct_mapreduce_tpu.storage.certdb import FilesystemDatabase
from ct_mapreduce_tpu.storage.mockbackend import MockBackend
from ct_mapreduce_tpu.storage.mockcache import MockRemoteCache
from ct_mapreduce_tpu.telemetry import metrics, trace

URL = "https://ct.example.com/wide"
LOG = "ct.example.com/wide"
ENTRY = {
    "leaf_input": base64.b64encode(
        leaflib.encode_leaf_input(b"\x30\x03\x02\x01\x01", 1700000000000)
    ).decode(),
    "extra_data": base64.b64encode(
        leaflib.encode_extra_data([b"\x30\x03\x02\x01\x02"])).decode(),
}


@pytest.fixture(autouse=True)
def clean_telemetry():
    trace.disable()
    metrics.set_sink(metrics.InMemSink())
    yield
    trace.disable()
    metrics.set_sink(metrics.InMemSink())


class PagedLog:
    """A log of ``tree_size`` entries that caps a response at ``page``."""

    def __init__(self, tree_size: int, page: int):
        self.tree_size = tree_size
        self.page = page
        self.starts: list[int] = []  # get-entries requests, in order
        self._full = self._body(page)

    @staticmethod
    def _body(n: int) -> bytes:
        return json.dumps({"entries": [ENTRY] * n}).encode()

    def transport(self, url: str):
        parsed = urlparse(url)
        if parsed.path.endswith("/ct/v1/get-sth"):
            return 200, {}, json.dumps(
                {"tree_size": self.tree_size,
                 "timestamp": 1700000000000}).encode()
        q = parse_qs(parsed.query)
        start, end = int(q["start"][0]), int(q["end"][0])
        n = min(end, start + self.page - 1, self.tree_size - 1) - start + 1
        self.starts.append(start)
        return 200, {}, (self._full if n == self.page else self._body(n))


class GatedSink:
    """Counts what passes through it; while ``gate`` is clear every
    store call stands still, as a sink does for the length of a batch's
    decode, submit and fold."""

    def __init__(self, flush_size: int = 4096, open_: bool = False):
        self.flush_size = flush_size
        self.gate = threading.Event()
        if open_:
            self.gate.set()
        self.entered = threading.Event()
        self.stored = 0
        self.flushed = 0
        self._lock = threading.Lock()

    def _take(self, n: int) -> None:
        self.entered.set()
        assert self.gate.wait(timeout=60), "the test never opened the gate"
        with self._lock:
            self.stored += n

    def store_raw_batch(self, raw) -> None:
        self._take(len(raw))

    def store(self, entry, log_url) -> None:
        self._take(1)

    def flush(self) -> None:
        self.flushed += 1


class RecordingDb(FilesystemDatabase):
    """Every cursor write beside what had passed through the sink when
    it was written."""

    def __init__(self, sink: GatedSink):
        super().__init__(MockBackend(), MockRemoteCache())
        self.sink = sink
        self.saves: list[tuple[int, int]] = []

    def save_log_state(self, log) -> None:
        self.saves.append((log.max_entry, self.sink.stored))
        super().save_log_state(log)


def wait_until(cond, timeout: float = 60.0, what: str = "") -> None:
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, f"timed out: {what}"
        time.sleep(0.005)


def settle(log: PagedLog, engine: LogSyncEngine, requests: int = 0) -> None:
    """Wait until the downloader stands in ``put`` on a full channel
    and see that it stays there: the channel at its bound, one page in
    the sink's hands and one in the downloader's (``requests`` where a
    page is not an item), and no request for two put timeouts more."""
    ch = engine.entry_queue
    wait_until(lambda: ch.qsize() >= ch.capacity
               and len(log.starts) == (requests or ch.depth() + 2),
               what="downloader blocked on a full channel")
    seen = len(log.starts)
    time.sleep(0.6)
    assert len(log.starts) == seen, "the downloader fetched past a full channel"


def start(log: PagedLog, sink, db=None, **kw) -> LogSyncEngine:
    db = db or FilesystemDatabase(MockBackend(), MockRemoteCache())
    engine = LogSyncEngine(sink, db, num_threads=1, **kw)
    engine.start_store_threads()
    engine.sync_log(URL, transport=log.transport)
    return engine


def finish(engine: LogSyncEngine) -> None:
    engine.wait_for_downloads(timeout=120)
    assert not engine._download_threads, "a downloader never finished"
    engine.stop()
    assert not engine.errors, engine.errors


# -- (1) the bound is entries: one batch of the sink, whatever the page ----


@pytest.mark.parametrize("page", [32, 256, 512, 1000])
def test_downloader_runs_one_batch_ahead_of_a_stalled_sink(page):
    """``flush_size`` 65,536: with the store thread standing in its
    sink, the downloader enqueues one batch of entries (within one
    page), whatever a response carries, and then blocks."""
    batch = 65536
    log = PagedLog(batch + 6 * page + 7, page)
    sink = GatedSink(flush_size=batch)
    engine = start(log, sink, raw_batches=True)
    assert engine.entry_queue.capacity == batch
    settle(log, engine)
    held = engine.entry_queue.qsize()
    assert batch <= held < batch + page
    assert engine.entry_queue.depth() == -(-batch // page)
    # One page in the sink's hands, one in the downloader's (in `put`).
    assert len(log.starts) == engine.entry_queue.depth() + 2
    assert sink.stored == 0
    gauges = metrics.get_sink().snapshot()["gauges"]
    assert gauges["ingest.channel_capacity_entries"] == batch
    assert gauges["ingest.channel_high_water_entries"] == held
    sink.gate.set()
    finish(engine)
    assert sink.stored == log.tree_size
    assert engine.database.get_log_state(LOG).max_entry == log.tree_size
    assert engine.entry_queue.high_water < batch + page


@pytest.mark.parametrize("page", [32, 512, 1000])
def test_small_batch_sink_gets_its_own_batch(page):
    """A sink with a small batch (its default is 4,096) pauses for a
    small batch: the channel holds that batch and no reference constant
    (``benchmark/tests/rehearse.py`` runs 1,024 lanes over 64-entry
    pages and needs its downloader to meet a full channel)."""
    batch = 4096
    log = PagedLog(batch + 6 * page + 7, page)
    sink = GatedSink(flush_size=batch)
    engine = start(log, sink, raw_batches=True)
    assert engine.entry_queue.capacity == batch
    settle(log, engine)
    assert batch <= engine.entry_queue.qsize() < batch + page
    assert engine.entry_queue.depth() == -(-batch // page)
    sink.gate.set()
    finish(engine)
    assert sink.stored == log.tree_size


def test_per_entry_mode_keeps_16384_items():
    """``raw_batches=False`` (every backend but ``tpu``): 16,384
    entries, an entry an item, whatever the sink's batch."""
    log = PagedLog(ENTRY_QUEUE_CAPACITY + 2000, 1000)
    sink = GatedSink(flush_size=65536)
    engine = start(log, sink)
    assert engine.entry_queue.capacity == 16384
    settle(log, engine, requests=17)  # 16,384 + the sink's + the downloader's
    assert engine.entry_queue.qsize() == engine.entry_queue.depth() == 16384
    sink.gate.set()
    finish(engine)
    assert sink.stored == log.tree_size


def test_queue_capacity_stays_the_per_entry_bound():
    sink = GatedSink(flush_size=64)
    assert LogSyncEngine(sink, None, queue_capacity=10) \
        .entry_queue.capacity == 10
    # Raw batches: one batch of the sink's, whatever the per-entry bound.
    for per_entry in (10, 100, ENTRY_QUEUE_CAPACITY):
        assert LogSyncEngine(sink, None, queue_capacity=per_entry,
                             raw_batches=True).entry_queue.capacity == 64

    class NoBatch:  # `flush_size` is part of what a raw-batch sink is
        pass

    with pytest.raises(AttributeError, match="flush_size"):
        LogSyncEngine(NoBatch(), None, raw_batches=True)
    assert LogSyncEngine(NoBatch(), None).entry_queue.capacity == 16384


# -- (2) odd page sizes ------------------------------------------------------


def page_of(n: int, start_index: int = 0) -> RawBatch:
    return RawBatch([ENTRY["leaf_input"]] * n, [ENTRY["extra_data"]] * n,
                    start_index=start_index, log_url=URL)


def test_page_larger_than_the_room_left_is_admitted_under_the_bound():
    ch = _EntryChannel(1000)
    ch.put(page_of(900), timeout=0)
    ch.put(page_of(512), timeout=0)  # 100 of room, 512 go in
    assert (ch.qsize(), ch.depth(), ch.high_water) == (1412, 2, 1412)
    with pytest.raises(queue.Full):
        ch.put(page_of(1), timeout=0.05)
    assert len(ch.get()) == 900  # 512 left: under the bound again
    ch.put(page_of(512), timeout=0)
    assert (ch.qsize(), ch.depth()) == (1024, 2)
    assert ch.full() and not ch.empty()


def test_page_larger_than_the_whole_bound_does_not_deadlock():
    ch = _EntryChannel(100)
    ch.put(page_of(256), timeout=0)
    assert ch.qsize() == 256
    assert len(ch.get()) == 256
    assert ch.empty() and ch.qsize() == 0


def test_sync_with_pages_larger_than_the_bound_completes():
    log = PagedLog(256 * 9 + 5, 256)
    sink = GatedSink(flush_size=100, open_=True)
    engine = start(log, sink, raw_batches=True)
    assert engine.entry_queue.capacity == 100
    finish(engine)
    assert sink.stored == log.tree_size
    assert engine.database.get_log_state(LOG).max_entry == log.tree_size
    assert engine.entry_queue.high_water == 256  # never two at once


@pytest.mark.parametrize("item", [None, page_of(0)], ids=["stop", "empty"])
def test_an_item_of_no_entries_still_wakes_a_reader(item):
    """The ``None`` a store thread stops on and an empty response weigh
    one: ``get`` reads a channel of weight 0 as empty."""
    ch = _EntryChannel(100)
    got = []
    t = threading.Thread(target=lambda: got.append(ch.get()), daemon=True)
    t.start()
    ch.put(item)
    t.join(timeout=10)
    assert not t.is_alive() and got == [item]
    assert ch.qsize() == 0 and ch.empty()


def test_one_get_lets_every_waiting_small_page_in():
    """A ``get`` wakes one putter; where its page leaves room the next
    is woken too (three downloaders behind one large page)."""
    ch = _EntryChannel(100)
    ch.put(page_of(100))
    threads = [threading.Thread(target=ch.put, args=(page_of(10),),
                                daemon=True) for _ in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.2)
    assert ch.depth() == 1
    assert len(ch.get()) == 100
    for t in threads:
        t.join(timeout=10)
    assert not any(t.is_alive() for t in threads)
    assert (ch.qsize(), ch.depth()) == (30, 3)


# -- (3) the cursor never passes what the sink has not had -------------------


@pytest.mark.parametrize("stalled", [True, False],
                         ids=["channel-full", "channel-empty"])
def test_cursor_never_passes_the_sink(stalled):
    """Every durable cursor write covers only entries that have been
    through the sink: with the sink stalled and the channel full (a save
    does not return until the sink is released) and with a sink that
    keeps the channel empty (a save after every page)."""
    page, batch = 256, 2048
    log = PagedLog(batch + 10 * page + 3, page)
    sink = GatedSink(flush_size=batch, open_=not stalled)
    db = RecordingDb(sink)
    hooks = []
    engine = start(
        log, sink, db, raw_batches=True,
        save_period_s=1e9 if stalled else 0.0,
        checkpoint_hook=lambda: hooks.append(sink.stored))
    if stalled:
        settle(log, engine)
        engine.checkpoint_now()  # the fleet's tick: save at the next page
        saved = threading.Event()
        threading.Thread(
            target=lambda: (engine._pre_cursor_save(URL), saved.set()),
            daemon=True).start()
        assert not saved.wait(timeout=0.7), \
            "a cursor save returned with a batch still in the channel"
        assert db.saves == [] and hooks == []
        assert db.get_log_state(LOG).max_entry == 0
        sink.gate.set()
        assert saved.wait(timeout=60)
    finish(engine)
    assert sink.stored == log.tree_size
    assert db.saves and db.saves[-1][0] == log.tree_size
    assert len(db.saves) >= (2 if stalled else len(log.starts))
    for cursor, through_sink in db.saves:
        assert cursor <= through_sink, db.saves
    # The hook ran before each write that moved the cursor, with the
    # log's entries all stored (the exit save after a tick that already
    # covered the last page writes the same cursor and no checkpoint).
    assert len(hooks) >= len({cursor for cursor, _ in db.saves})


def test_stop_while_blocked_in_put_leaves_the_cursor_on_that_page():
    """``stop_event`` set while the downloader stands in ``put``: the
    page in its hands never reached a worker, the exit save waits for
    what did and stops before it, and the next run fetches it again."""
    page, batch = 256, 2048
    log = PagedLog(batch + 10 * page + 3, page)
    sink = GatedSink(flush_size=batch)
    db = RecordingDb(sink)
    engine = start(log, sink, db, raw_batches=True)
    settle(log, engine)
    in_hand = log.starts[-1]
    engine.signal_stop()
    time.sleep(0.6)  # the exit save now waits for the channel's entries
    assert db.saves == []
    sink.gate.set()
    finish(engine)
    assert db.saves == [(in_hand, in_hand)]
    assert sink.stored == in_hand < log.tree_size
    assert sink.flushed == 1
    # Resume: the page that never reached a worker is the first fetched.
    again = LogSyncEngine(sink, db, num_threads=1, raw_batches=True)
    again.start_store_threads()
    asked = len(log.starts)
    again.sync_log(URL, transport=log.transport)
    finish(again)
    assert log.starts[asked] == in_hand
    assert sink.stored == log.tree_size
    assert db.get_log_state(LOG).max_entry == log.tree_size


# -- (4) stop() drains --------------------------------------------------------


@pytest.mark.parametrize("threads", [1, 3])
def test_stop_drains_a_full_channel_and_joins(threads):
    sink = GatedSink(flush_size=1024)
    engine = LogSyncEngine(sink, None, num_threads=threads,
                           raw_batches=True)
    for n in [128] * 7 + [256]:  # 1,152 entries: the last page overshoots
        item = page_of(n)
        engine.entry_queue.put(item, timeout=0)
        engine._account_enqueued(item)
    assert engine.entry_queue.full()
    engine.start_store_threads()
    assert sink.entered.wait(timeout=10)
    stopped = threading.Event()
    threading.Thread(target=lambda: (engine.stop(), stopped.set()),
                     daemon=True).start()
    assert not stopped.wait(timeout=0.5)  # it waits for the sink
    sink.gate.set()
    assert stopped.wait(timeout=60)
    assert sink.stored == 1152 and sink.flushed == 1
    assert engine.entry_queue.qsize() == engine.entry_queue.depth() == 0
    assert engine._store_threads == [] and not engine.errors
    assert engine._outstanding[URL] == 0


# -- what says it engages ------------------------------------------------------


def test_enqueue_span_carries_items_and_entries():
    trace.enable()
    page = 64
    log = PagedLog(page * 6, page)
    sink = GatedSink(flush_size=page * 4)
    engine = start(log, sink, raw_batches=True)
    settle(log, engine)
    sink.gate.set()
    finish(engine)
    puts = [e["args"] for e in trace.snapshot_events()
            if e["ph"] == "X" and e["name"] == "fetch.enqueue"]
    assert len(puts) == 6
    for args in puts:
        assert args["entries"] == args["depth"] * page
    # The sixth found the channel full: four pages, one batch.
    assert max(a["entries"] for a in puts) == page * 4
