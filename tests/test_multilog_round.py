"""A round of several logs (``LogSyncEngine._exit_save``, ingest/sync.py):
three logs of unequal length into one ``AggregatorSink`` end with the
counts and cursors of a plain reference (``tests/reference_multilog.py``);
the round writes ONE checkpoint however many logs it has, none for a log
that gave nothing, and a ``savePeriod`` tick still saves at once; at no
instant is a cursor on disk ahead of the checkpoint on disk; a log does
not wait for logs that stand still; a chunk short of the batch takes the
program whole batches use; and one log alone reads as it always did.

The logs serve the benchmark's committed templates
(``benchmark/fixture.py``, which imports nothing of the program).
"""

import base64
import datetime
import json
import os
import shutil
import sys
import threading
import time
import warnings
from urllib.parse import parse_qs, urlparse

import pytest

import benchmark.fixture as fx
from ct_mapreduce_tpu.agg import aggregator as agglib
from ct_mapreduce_tpu.agg import ckpt
from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
from ct_mapreduce_tpu.core.types import CertificateLog
from ct_mapreduce_tpu.ingest import leaf as leaflib
from ct_mapreduce_tpu.ingest.sync import (
    AggregatorSink,
    LogSyncEngine,
    RawBatch,
)
from ct_mapreduce_tpu.native import leafpack
from ct_mapreduce_tpu.storage.certdb import FilesystemDatabase
from ct_mapreduce_tpu.storage.mockbackend import MockBackend
from ct_mapreduce_tpu.storage.mockcache import MockRemoteCache
from ct_mapreduce_tpu.telemetry import metrics, trace
from ct_mapreduce_tpu.utils import minicert
from tests.reference_multilog import Reference
from tests.test_entry_channel import PagedLog

NOW = datetime.datetime(2026, 1, 1, tzinfo=datetime.timezone.utc)
PAGE, BATCH = 16, 64
# One log ends a batch and a half before the longest, one is empty, and
# the total (368) is no whole number of batches.
LENGTHS = (232, 136, 0)
TPL = fx.Templates()


@pytest.fixture(autouse=True)
def clean_telemetry():
    trace.disable()
    metrics.set_sink(metrics.InMemSink())
    # Process-wide span attributes are the worker process's, not a
    # test's: a fleet test that ran in it before (``ingest/fleet.py``
    # stamps ``epoch`` on every later span, and only ``ct_fetch.main``
    # takes it off) is no part of the spans compared whole here.
    trace.set_process_attrs(**dict.fromkeys(trace.get_process_attrs()))
    yield
    trace.disable()
    metrics.set_sink(metrics.InMemSink())


class TemplateLog:
    """Log ``index`` of a run: ``entries`` entries of the committed
    templates from ``seed`` (serial spaces disjoint by log), of which
    ``size`` are in the tree so far. ``gate``, when set to an Event,
    holds every get-entries request from ``hold_from`` on until the
    test sets it: a transport that blocks."""

    def __init__(self, index: int, entries: int, seed: int):
        self.url = f"https://ct.example.com/log{index}"
        self.short = f"ct.example.com/log{index}"
        self.fixture = None
        if entries:
            spec = fx.LogSpec(
                logs=1, page=PAGE, dup_share=0.1,
                leaf_mix={"rsa2048": 0.7, "ec_p256": 0.3}, issuers=4,
                zipf_s=1.1, warmup_entries=0, window_entries=entries)
            self.fixture = fx.LogFixture(spec, seed, index)
        self.size = entries
        self.gate = None
        self.hold_from = 0

    def body(self, start: int, end: int) -> bytes:
        return self.fixture.page_body(TPL, start, end)

    def transport(self, url: str):
        parsed = urlparse(url)
        if parsed.path.endswith("/ct/v1/get-sth"):
            return 200, {}, json.dumps({
                "tree_size": self.size, "timestamp": fx.TS_BASE_MS}).encode()
        q = parse_qs(parsed.query)
        start, end = int(q["start"][0]), int(q["end"][0])
        if self.gate is not None and start >= self.hold_from:
            assert self.gate.wait(timeout=120), "the test never let go"
        return 200, {}, self.body(start, min(end, self.size - 1))


def make_logs(seed: int, lengths=LENGTHS) -> list[TemplateLog]:
    return [TemplateLog(k, n, seed) for k, n in enumerate(lengths)]


def reference_of(logs) -> Reference:
    ref = Reference()
    for log in logs:
        ref.cursors[log.short] = 0
        for start in range(0, log.size, PAGE):
            ref.feed(log.short, start,
                     log.body(start, min(start + PAGE, log.size) - 1))
    return ref


class Run:
    """The engine as ct-fetch wires it in TPU mode (raw batches, one
    store thread, the checkpoint hook, the round's own save at its
    end), over a state directory and a cursor store that outlive it."""

    def __init__(self, state_dir, db=None, save_period_s: float = 1e9,
                 sink=None):
        self.path = os.path.join(str(state_dir), "agg.npz")
        self.db = db or FilesystemDatabase(MockBackend(), MockRemoteCache())
        self.agg = TpuAggregator(capacity=1 << 12, batch_size=BATCH, now=NOW)
        if os.path.exists(self.path):
            self.agg.load_checkpoint(self.path)
        self.sink = sink or AggregatorSink(self.agg, flush_size=BATCH)
        self.engine = LogSyncEngine(
            self.sink, self.db, num_threads=1, raw_batches=True,
            save_period_s=save_period_s,
            checkpoint_hook=lambda: self.sink.checkpointed_save(self.save))

    def save(self) -> None:
        self.agg.save_checkpoint(self.path)

    def round(self, logs) -> None:
        """One pass of ct-fetch's round loop."""
        engine = self.engine
        engine.start_store_threads()
        for log in logs:
            engine.sync_log(log.url, transport=log.transport)
        engine.wait_for_downloads(timeout=180)
        assert not engine._download_threads, "a downloader never ended"
        engine.stop()
        self.save()
        assert not engine.errors, engine.errors

    def cursors(self, logs) -> dict[str, int]:
        return {log.short: self.db.get_log_state(log.short).max_entry
                for log in logs}

    def close(self) -> None:
        self.sink.close()


def spans(name: str = "") -> list[dict]:
    return [e for e in trace.snapshot_events()
            if e["ph"] == "X" and (not name or e["name"] == name)]


def end_of(e: dict) -> float:
    return e["ts"] + e["dur"]


# -- (a) counts and cursors are the reference's -------------------------------


@pytest.mark.parametrize("seed", [11, 2147483659, 31337])
def test_three_unequal_logs_agree_with_the_plain_reference(tmp_path, seed):
    logs = make_logs(seed)
    ref = reference_of(logs)
    run = Run(tmp_path)
    run.round(logs)
    snap = run.agg.drain()
    run.close()
    assert ref.entries == sum(LENGTHS) and ref.unique() < ref.entries
    assert snap.counts == ref.counts()
    assert snap.total == ref.unique()
    assert run.cursors(logs) == ref.cursors
    # ... and from the files alone, as storage-statistics reads them.
    cold = TpuAggregator(capacity=1 << 12, batch_size=BATCH, now=NOW)
    cold.load_checkpoint(run.path)
    assert cold.drain().counts == ref.counts()


# -- (b) one checkpoint a round -----------------------------------------------


def test_a_round_of_three_logs_writes_one_checkpoint(tmp_path):
    """One ``kind=full`` for the round (then the round's own save finds
    nothing to write), caused by the last log to end; the earlier log's
    cursor is written after it, under the same ``round.save``; the empty
    log saves nothing. The round's total is no whole number of batches,
    so the one flush dispatches one short chunk."""
    trace.enable()
    logs = make_logs(21)
    run = Run(tmp_path)
    run.round(logs)
    run.close()
    saves = spans("ckpt.save")
    assert [s["args"]["kind"] for s in saves] == ["full", "noop"]
    full = saves[0]
    assert full["args"]["reason"] == "exit"
    waits = {e["args"]["log"]: e for e in spans("round.cursor_wait")}
    hows = {log: e["args"]["how"] for log, e in waits.items()}
    assert hows[logs[2].short] == "unmoved"
    # Whichever of the two ended last closed the round for both.
    assert sorted(hows[log.short] for log in logs[:2]) == ["closed", "covered"]
    early = next(waits[log.short] for log in logs[:2]
                 if hows[log.short] == "covered")
    assert [waits[log.short]["args"]["position"] for log in logs] \
        == list(LENGTHS)
    (round_save,) = spans("round.save")
    assert round_save["args"] == {
        "reason": "exit", "logs": 3, "cursors": 2,
        "entries": LENGTHS[0] + LENGTHS[1]}
    assert full["parent"] == round_save["id"]
    cursors = spans("fetch.save_cursor")
    moved = [c for c in cursors if c["args"]["position"]]
    assert sorted(c["args"]["log"] for c in moved) \
        == [logs[0].short, logs[1].short]
    for c in moved:  # the aggregate on disk first, then the cursor
        assert c["parent"] == round_save["id"]
        assert end_of(full) <= c["ts"]
    # The empty log: a cursor write that waited for no checkpoint.
    (empty,) = [c for c in cursors if not c["args"]["position"]]
    assert empty["parent"] == waits[logs[2].short]["id"]
    assert not [e for e in spans() if e["parent"] == empty["id"]]
    # The log that ended first waited from its last page to its cursor.
    assert end_of(early) >= end_of(full)
    # Every dispatch is one batch of lanes; what the entries leave
    # empty was padded (the last flush, and a cut that an odd page made
    # longer than the batch).
    counters = metrics.get_sink().snapshot()["counters"]
    steps = len(spans("device.step"))
    assert counters["ingest.partial_batches"] >= 1
    assert counters["ingest.partial_lanes"] == steps * BATCH - sum(LENGTHS)
    # Which pages meet in a cut is the threads' business (the channel
    # hands over a batch of one log as often as not); that a cut of two
    # logs says so is held below, where the test feeds the sink itself.
    cuts = [e["args"] for e in spans("sink.accumulate") if "batch" in e["args"]]
    assert cuts and all(
        c["logs"] == len({log for log, _first, _last in c["pages"]})
        for c in cuts)


def test_a_save_period_tick_inside_the_round_saves_at_once(tmp_path):
    """``savePeriod`` 0: every page boundary is a tick, and each tick
    writes its checkpoint there and then (a full base while another
    downloader still fetches), not at the round's end. Counts stay the
    reference's."""
    trace.enable()
    logs = make_logs(22)
    ref = reference_of(logs)
    run = Run(tmp_path, save_period_s=0.0)
    run.round(logs)
    snap = run.agg.drain()
    run.close()
    assert snap.counts == ref.counts() and run.cursors(logs) == ref.cursors
    ticks = [s for s in spans("ckpt.save")
             if s["args"].get("reason") == "savePeriod"]
    assert len([s for s in ticks if s["args"]["kind"] == "full"]) >= 3
    by_id = {e["id"]: e for e in spans()}
    for s in ticks:
        assert by_id[s["parent"]]["name"] == "fetch.save_cursor"
    last_page = max(end_of(e) for e in spans("fetch.page"))
    assert min(end_of(s) for s in ticks) < last_page


# -- (c) no cursor on disk is ever ahead of the checkpoint on disk ------------


def test_a_restart_from_any_instant_of_the_round_ends_with_the_reference(
        tmp_path, monkeypatch):
    """Two rounds (the logs grow between them). At every
    ``ckpt.kill_point`` and after every cursor write, what is on disk
    (the checkpoint's files and the cursors) is kept; a fresh process
    started from each of them and run to the logs' end has the
    reference's counts: an entry behind a cursor is never missing from
    the aggregate beside it."""
    logs = make_logs(23)
    ref = reference_of(logs)
    state = tmp_path / "state"
    state.mkdir()
    run = Run(state)
    kept: list[tuple[str, str, dict]] = []
    keeping = threading.Lock()  # the empty log's cursor has a thread of its own

    def keep(point: str) -> None:
        with keeping:
            where = tmp_path / f"kept{len(kept)}"
            shutil.copytree(state, where)
            kept.append((point, str(where), run.cursors(logs)))

    real_save = run.db.save_log_state

    def save_log_state(log):
        real_save(log)
        keep("cursor:" + log.short_url)

    monkeypatch.setattr(ckpt, "kill_point", keep)
    monkeypatch.setattr(run.db, "save_log_state", save_log_state)
    for log, first in zip(logs, (120, 72, 0)):
        log.size = first
    run.round(logs)
    for log, n in zip(logs, LENGTHS):
        log.size = n
    run.round(logs)
    run.close()
    monkeypatch.undo()
    assert run.agg.drain().counts == ref.counts()
    points = {p.split(":")[0] for p, _d, _c in kept}
    assert {"cursor", "base-post-rename", "manifest-pre-rename"} <= points, \
        points
    assert len(kept) >= 10
    for point, where, cursors in kept:
        db = FilesystemDatabase(MockBackend(), MockRemoteCache())
        for short, position in cursors.items():
            db.save_log_state(CertificateLog(short_url=short,
                                             max_entry=position))
        again = Run(where, db=db)
        again.round(logs)
        got = again.agg.drain()
        again.close()
        assert got.counts == ref.counts(), (point, cursors)
        assert again.cursors(logs) == ref.cursors, point


def test_a_save_covers_a_chunk_cut_before_it(monkeypatch):
    """A chunk that the store thread has cut and not yet handed to the
    device is neither pending nor in flight, and its entries already
    count as stored for their logs' cursors. A checkpoint asked for in
    that instant (another log's cursor save) waits for it: what it
    saves holds the chunk. Held open here by a decode that blocks; the
    restart test above met the same instant once in a few runs, as a
    cursor ahead of the checkpoint."""
    if leafpack.load_native() is None:
        pytest.skip("the raw-batch path needs the native decoder")
    agg = TpuAggregator(capacity=1 << 12, batch_size=BATCH, now=NOW)
    sink = AggregatorSink(agg, flush_size=BATCH)
    cut, let_go = threading.Event(), threading.Event()
    real = sink._prepare_chunk

    def prepare(chunk):
        cut.set()
        assert let_go.wait(timeout=120), "the test never let go"
        return real(chunk)

    monkeypatch.setattr(sink, "_prepare_chunk", prepare)
    saved: list[int] = []
    # Two logs' pages in turn, so the one cut holds both.
    pages = [raw_page(log.url, start, json.loads(
        log.body(start, start + PAGE - 1))["entries"])
        for start in range(0, BATCH // 2, PAGE)
        for log in make_logs(25, (BATCH // 2, BATCH // 2))]
    trace.enable()
    store = threading.Thread(target=lambda: [
        sink.store_raw_batch(raw) for raw in pages])
    saver = threading.Thread(target=lambda: sink.checkpointed_save(
        lambda: saved.append(agg.metrics["inserted"] + agg.metrics["known"])))
    store.start()
    assert cut.wait(timeout=120)
    saver.start()
    saver.join(timeout=0.5)  # a save that did not wait is over by now
    let_go.set()
    store.join(timeout=120)
    saver.join(timeout=120)
    sink.close()
    assert saved == [BATCH]
    (cut_args,) = [e["args"] for e in spans("sink.accumulate")
                   if "batch" in e["args"]]
    assert cut_args["logs"] == 2 and len(cut_args["pages"]) == len(pages)


# -- (d) a short chunk takes the one program ----------------------------------


class Compiles:
    """What XLA compiled, from JAX's monitoring events (as
    ``benchmark/harness.py::CompileLog`` counts a run's)."""

    def __init__(self):
        self.n = 0

    def __call__(self, event: str, _seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *_exc):
        from jax._src import monitoring

        monitoring.unregister_event_duration_listener(self)


def template_pages(entries: int) -> list[RawBatch]:
    log = TemplateLog(0, entries, 24)
    return [raw_page(log.url, start, json.loads(
        log.body(start, min(start + PAGE, entries) - 1))["entries"])
        for start in range(0, entries, PAGE)]


def small_pages(entries: int) -> list[RawBatch]:
    """Hand-assembled certificates of a few hundred bytes: every row
    fits the narrow width."""
    ca = minicert.make_cert(serial=1, issuer_cn="Narrow CA", is_ca=True)
    extra = base64.b64encode(leaflib.encode_extra_data([ca])).decode()
    rows = [{"leaf_input": base64.b64encode(leaflib.encode_leaf_input(
        minicert.make_cert(serial=1000 + j, issuer_cn="Narrow CA",
                           subject_cn="n.example", is_ca=False),
        1000 + j)).decode(), "extra_data": extra} for j in range(entries)]
    return [raw_page("https://ct.example.com/narrow", start,
                     rows[start:start + PAGE])
            for start in range(0, entries, PAGE)]


def raw_page(url: str, start: int, rows: list[dict]) -> RawBatch:
    return RawBatch([r["leaf_input"] for r in rows],
                    [r["extra_data"] for r in rows], start, url)


@pytest.mark.parametrize("pages_of, width, short_first, batch", [
    (template_pages, AggregatorSink.PAD_LEN, False, 64),
    (template_pages, AggregatorSink.PAD_LEN, True, 48),
    (small_pages, AggregatorSink.PAD_LEN // 2, False, 32),
    (small_pages, AggregatorSink.PAD_LEN // 2, True, 80)],
    ids=["full-whole-then-short", "full-short-then-whole",
         "narrow-whole-then-short", "narrow-short-then-whole"])
def test_a_short_chunk_compiles_nothing_new(monkeypatch, pages_of, width,
                                            short_first, batch):
    """On a backend that donates (every one but the CPU; steered here)
    whole batches reach the step as device arrays and a chunk short of
    the batch as NumPy rows padded on the host. Both are one program:
    whichever comes second compiles nothing, at either row width. (A
    batch size to each case, so that each compiles its own program
    whatever ran before it in this process.)"""
    if leafpack.load_native() is None:
        pytest.skip("the raw-batch path needs the native decoder")
    import jax
    import numpy as np

    monkeypatch.setattr(agglib, "_donating_backend", lambda: True)
    agg = TpuAggregator(capacity=1 << 12, batch_size=batch, now=NOW)
    sink = AggregatorSink(agg, flush_size=batch)
    pages = pages_of(batch + 24)
    whole, short = pages[:batch // PAGE], pages[batch // PAGE:]
    seen: list[tuple[bool, tuple]] = []
    real = agg._device_step_packed

    def step(packed):
        seen.append((isinstance(packed.data, jax.Array), packed.data.shape))
        return real(packed)

    monkeypatch.setattr(agg, "_device_step_packed", step)

    def feed(chunk) -> int:
        with Compiles() as compiles:
            for raw in chunk:
                sink.store_raw_batch(raw)
            sink.flush()
        return compiles.n

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # the CPU cannot use the donation
        first, second = (short, whole) if short_first else (whole, short)
        assert feed(first) >= 1  # the one program (and the fold's own)
        assert feed(second) == 0
    sink.close()
    # The whole batch came as a device array, the short chunk as NumPy
    # rows of the same shape.
    assert sorted(seen) == [(False, (batch, width)), (True, (batch, width))]
    assert not isinstance(np.zeros(1), jax.Array)
    counters = metrics.get_sink().snapshot()["counters"]
    assert counters["ct-fetch.insertCertificate"] == batch + 24
    assert counters["ingest.partial_batches"] == 1
    assert counters["ingest.partial_lanes"] == batch - 24


# -- (e) a parked log does not wait for logs that stand still -----------------


def test_a_log_saves_alone_once_the_others_stand_still(tmp_path, monkeypatch):
    """Log 1 ends while log 0's transport blocks after its first pages:
    nothing of log 0 goes into the channel or through the sink, so after
    the bound log 1 saves as a log alone would (its own flush and
    checkpoint, its cursor durable) while log 0 still stands. Let go,
    log 0 runs to its end, closes the round, and the counts are the
    reference's."""
    monkeypatch.setattr(LogSyncEngine, "STILL_FLOOR_S", 0.5)
    trace.enable()
    logs = make_logs(25, lengths=(232, 136))
    ref = reference_of(logs)
    logs[0].gate, logs[0].hold_from = threading.Event(), 2 * PAGE
    run = Run(tmp_path)
    engine = run.engine
    engine.start_store_threads()
    for log in logs:
        engine.sync_log(log.url, transport=log.transport)
    deadline = time.monotonic() + 120
    while run.cursors(logs)[logs[1].short] != LENGTHS[1]:
        assert time.monotonic() < deadline, "log 1 never saved"
        time.sleep(0.02)
    assert run.cursors(logs)[logs[0].short] == 0  # log 0 stands still
    assert len(engine._download_threads) == 2
    (gave_up,) = [e for e in spans("round.cursor_wait")
                  if e["args"]["log"] == logs[1].short]
    assert gave_up["args"]["how"] == "gave_up"
    assert gave_up["dur"] / 1e6 >= 0.5
    logs[0].gate.set()
    engine.wait_for_downloads(timeout=120)
    assert not engine._download_threads
    engine.stop()
    run.save()
    run.close()
    assert not engine.errors, engine.errors
    assert run.agg.drain().counts == ref.counts()
    assert run.cursors(logs) == ref.cursors
    hows = {e["args"]["log"]: e["args"]["how"]
            for e in spans("round.cursor_wait")}
    assert hows == {logs[0].short: "closed", logs[1].short: "gave_up"}
    fulls = [s for s in spans("ckpt.save") if s["args"]["kind"] == "full"]
    assert len(fulls) == 2  # log 1's own, then the round's


# -- (f) one log alone reads as it always did ---------------------------------


def test_one_log_alone_saves_full_then_noop(tmp_path):
    trace.enable()
    (log,) = make_logs(26, lengths=(200,))
    run = Run(tmp_path)
    run.round([log])
    run.close()
    saves = spans("ckpt.save")
    assert [s["args"]["kind"] for s in saves] == ["full", "noop"]
    assert saves[0]["args"]["reason"] == "exit"
    assert "reason" not in saves[1]["args"]
    (wait,) = spans("round.cursor_wait")
    assert wait["args"] == {"log": log.short, "position": 200,
                            "how": "closed"}
    (round_save,) = spans("round.save")
    assert round_save["args"] == {"reason": "exit", "logs": 1, "cursors": 1,
                                  "entries": 200}
    (cursor,) = spans("fetch.save_cursor")
    assert cursor["args"] == {"log": log.short, "position": 200,
                              "reason": "exit"}
    assert end_of(saves[0]) <= cursor["ts"]
    assert run.cursors([log]) == {log.short: 200}


# -- the protocol under a crowd ------------------------------------------------


class CountingSink:
    """A sink that counts a log's entries as they pass and never
    decodes: what a checkpoint would hold is what it has counted."""

    flush_size = 64

    def __init__(self):
        self.stored: dict[str, int] = {}
        self.lock = threading.Lock()

    def store_raw_batch(self, raw) -> None:
        with self.lock:
            self.stored[raw.log_url] = self.stored.get(raw.log_url, 0) \
                + len(raw)

    def flush(self) -> None:
        pass

    def held(self) -> dict[str, int]:
        with self.lock:
            return dict(self.stored)


class CrowdLog(PagedLog):
    """``tests/test_entry_channel.py``'s log of one tiny entry over and
    over, under a name of its own."""

    def __init__(self, index: int, page: int):
        super().__init__(0, page)
        self.url = f"https://ct.example.com/crowd{index}"
        self.short = f"ct.example.com/crowd{index}"


def test_a_crowd_of_logs_never_puts_a_cursor_ahead(monkeypatch):
    """Sixteen logs of every length (more downloaders than cores, the
    interpreter switching threads every few microseconds), with and
    without ticks, three rounds each: every cursor written is covered by
    the checkpoint written before it, every log ends at its tree head,
    and a round without ticks writes far fewer checkpoints than it has
    logs."""
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for save_period_s in (1e9, 0.0):
            sink = CountingSink()
            db = FilesystemDatabase(MockBackend(), MockRemoteCache())
            on_disk: list[dict[str, int]] = []
            ahead: list[tuple] = []
            real_save = db.save_log_state

            def save_log_state(log, _real=real_save, _disk=on_disk,
                               _ahead=ahead):
                covered = _disk[-1] if _disk else {}
                url = "https://" + log.short_url
                if log.max_entry > covered.get(url, 0):
                    _ahead.append((log.short_url, log.max_entry, covered))
                _real(log)

            monkeypatch.setattr(db, "save_log_state", save_log_state)
            engine = LogSyncEngine(
                sink, db, num_threads=2, raw_batches=True,
                save_period_s=save_period_s,
                checkpoint_hook=lambda _s=sink, _d=on_disk: _d.append(
                    _s.held()))
            logs = [CrowdLog(k, 8) for k in range(16)]
            for round_no in range(3):
                for k, log in enumerate(logs):
                    log.tree_size += (k * 37 + round_no * 11) % 90
                hooks_before = len(on_disk)
                engine.start_store_threads()
                for log in logs:
                    engine.sync_log(log.url, transport=log.transport)
                engine.wait_for_downloads(timeout=120)
                assert not engine._download_threads
                engine.stop()
                assert not engine.errors, engine.errors
                assert not ahead, ahead[:3]
                for log in logs:
                    assert db.get_log_state(log.short).max_entry \
                        == log.tree_size
                assert not engine._parked and not engine._fetching
                assert not engine._covered
                if save_period_s:
                    assert len(on_disk) - hooks_before <= 4
    finally:
        sys.setswitchinterval(before)
