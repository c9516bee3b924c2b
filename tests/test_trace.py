"""Span tracer (telemetry/trace.py): Chrome trace-event JSON schema,
span nesting, ring bound, the disabled fast path, and the per-stage
summary of tools/traceview.py."""

import json
import os
import statistics
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ct_mapreduce_tpu.telemetry import trace  # noqa: E402
from tools import traceview  # noqa: E402


@pytest.fixture(autouse=True)
def _fresh_tracer():
    prev = trace.get_tracer()
    trace.disable()
    yield
    trace.disable()  # ends the probe thread of a tracer a test enabled
    trace._tracer = prev


def _validate_schema(events):
    """The subset of the Trace Event Format this repo emits: complete
    spans (X: ts+dur), instants (i), thread metadata (M)."""
    assert events, "empty trace"
    for e in events:
        assert e["ph"] in ("X", "i", "M"), e
        assert isinstance(e["name"], str) and e["name"]
        assert isinstance(e["pid"], int)
        assert isinstance(e["tid"], int)
        if e["ph"] == "X":
            assert isinstance(e["ts"], (int, float)) and e["ts"] >= 0
            assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
            # The thread clock's fields, under the format's own names.
            assert isinstance(e["tts"], float) and e["tts"] >= 0
            assert 0 <= e["tdur"] <= e["dur"] + 50.0
        elif e["ph"] == "i":
            assert isinstance(e["ts"], (int, float))
            assert isinstance(e["tts"], float) and e["tts"] >= 0
        else:  # M
            assert e["name"] == "thread_name"
            assert isinstance(e["args"]["name"], str)


def _validate_nesting(events):
    """Per-tid stack discipline: any two X spans on a thread are
    disjoint or properly contained (what thread-local begin/end
    guarantees; Perfetto renders anything else as corrupt)."""
    by_tid = {}
    for e in events:
        if e.get("ph") == "X":
            by_tid.setdefault(e["tid"], []).append(e)
    for tid, evs in by_tid.items():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []
        for e in evs:
            end = e["ts"] + e["dur"]
            while stack and stack[-1] <= e["ts"]:
                stack.pop()
            if stack:
                assert end <= stack[-1] + 1e-3, (
                    f"tid {tid}: span {e['name']} crosses its parent")
            stack.append(end)


def test_export_schema_and_nesting(tmp_path):
    trace.enable(ring_size=1024)

    def worker(k):
        with trace.span("outer", cat="test", k=k):
            with trace.span("mid"):
                with trace.span("inner"):
                    time.sleep(0.002)
            trace.instant("tick", k=k)

    threads = [threading.Thread(target=worker, args=(k,), name=f"w{k}")
               for k in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    worker(99)  # main thread too

    path = str(tmp_path / "trace.json")
    assert trace.export(path) == path
    doc = json.load(open(path))
    assert "traceEvents" in doc
    events = doc["traceEvents"]
    _validate_schema(events)
    _validate_nesting(events)
    # 4 workers x 3 spans, 4 instants, >= 4 thread-name records (and
    # whatever the GIL probe's thread recorded meanwhile).
    xs = [e for e in events if e["ph"] == "X" and e["name"] != "gil.probe"]
    assert len(xs) == 12
    assert {e["name"] for e in xs} == {"outer", "mid", "inner"}
    assert sum(1 for e in events if e["ph"] == "i") == 4
    names = {e["args"]["name"] for e in events if e["ph"] == "M"}
    assert {"w0", "w1", "w2"} <= names
    # Span args survive the round trip.
    assert any(e.get("args", {}).get("k") == 99 for e in xs)


def test_disabled_is_shared_noop():
    """Tracing off: span() returns the SAME no-op object every call —
    no allocation on the hot path, nothing recorded."""
    assert not trace.enabled()
    a, b = trace.span("x"), trace.span("y", cat="c", k=1)
    assert a is b
    with a:
        pass
    trace.instant("nothing")
    assert trace.snapshot_events() == []
    assert trace.export() is None


def test_ring_bound():
    """The event ring is bounded (a week-long run cannot grow without
    limit) and keeps the newest window. Installed bare: the module's
    enable() starts the GIL probe, whose spans share the ring."""
    trace._tracer = trace.SpanTracer(ring_size=32)
    for i in range(200):
        with trace.span("s", i=i):
            pass
    xs = [e for e in trace.snapshot_events() if e["ph"] == "X"]
    assert len(xs) == 32
    assert xs[-1]["args"]["i"] == 199
    assert xs[0]["args"]["i"] == 168


def test_enable_idempotent_keeps_ring():
    trace.enable(ring_size=64)
    with trace.span("kept"):
        pass
    t2 = trace.enable(path="/tmp/whatever.json")
    assert any(e["name"] == "kept" for e in t2.events())
    assert t2.path == "/tmp/whatever.json"


def _spin(cpu_s: float) -> None:
    """Work on a core until the thread has used ``cpu_s`` of it."""
    t0 = time.thread_time()
    while time.thread_time() - t0 < cpu_s:
        pass


def _needs_a_fine_thread_clock():
    """The order tests below want a thread CPU clock that steps in
    microseconds, as Linux's does. Some sandboxes charge CPU time in
    scheduler ticks (the benchmark's chip host: 10 ms a step, PERF.md
    §6, PR 38); there a 50 ms span reads 40, 50 or 60."""
    t_end = time.perf_counter() + 0.05
    steps, last = [], time.thread_time_ns()
    while time.perf_counter() < t_end:
        now = time.thread_time_ns()
        if now != last:
            steps.append(now - last)
            last = now
    if not steps or min(steps) > 1_000_000:
        pytest.skip("the thread CPU clock steps in ticks here")


def _spans(tracer, name=None):
    return [e for e in tracer.events() if e["ph"] == "X"
            and (name is None or e["name"] == name)]


def test_a_span_tells_working_from_waiting():
    """``tdur`` is the thread's CPU time under the span: a span that
    sleeps has next to none of it, one that spins has most of its
    wall as CPU. Wide factors only: the machine may be loaded."""
    _needs_a_fine_thread_clock()
    tracer = trace.SpanTracer(ring_size=64)
    with tracer.span("asleep"):
        time.sleep(0.05)
    with tracer.span("spinning"):
        _spin(0.05)
    (asleep,), (spinning,) = _spans(tracer, "asleep"), _spans(tracer, "spinning")
    assert asleep["dur"] >= 50_000 and asleep["tdur"] < asleep["dur"] / 5
    assert spinning["tdur"] >= 50_000 * 0.99
    # Alone on a core the spin's CPU is nearly all of its wall; beside
    # a test suite's other workers it may be kept off the core for as
    # long again (118 ms of wall for 50 of CPU was seen under six), so
    # the floor is a tenth, and the order is what is held: the share of
    # its wall that a span spent on a core tells the two apart.
    on_core = [e["tdur"] / e["dur"] for e in (asleep, spinning)]
    assert on_core[1] > 0.1 and on_core[1] > 5 * on_core[0]
    for e in (asleep, spinning):
        assert 0 <= e["tdur"] <= e["dur"] + 50.0


def test_cpu_time_nests_and_the_thread_clock_rises_along_a_thread():
    _needs_a_fine_thread_clock()
    tracer = trace.SpanTracer(ring_size=64)

    def work():
        with tracer.span("parent"):
            _spin(0.005)
            with tracer.span("child"):
                _spin(0.01)
            time.sleep(0.005)
            with tracer.span("child"):
                _spin(0.002)
            tracer.instant("mark")
        with tracer.span("next"):
            pass

    t = threading.Thread(target=work, name="nester")
    t.start()
    t.join()
    work()  # and on this thread, whose clock did not start at zero
    for tid in {e["tid"] for e in _spans(tracer)}:
        mine = [e for e in tracer.events()
                if e["ph"] in ("X", "i") and e["tid"] == tid]
        parent = next(e for e in mine if e["name"] == "parent")
        kids = [e for e in mine if e["name"] == "child"]
        assert [k["parent"] for k in kids] == [parent["id"]] * 2
        for k in kids:  # inside the parent's, on the thread's own clock
            assert parent["tts"] <= k["tts"]
            assert k["tts"] + k["tdur"] <= parent["tts"] + parent["tdur"] + 1
        assert sum(k["tdur"] for k in kids) <= parent["tdur"] + 1
        assert parent["tdur"] >= 17_000 * 0.99
        # In the order the thread began them (ids are handed out at
        # entry), the thread clock never runs backwards.
        by_start = sorted(mine, key=lambda e: e["id"])
        assert [e["name"] for e in by_start] == [
            "parent", "child", "child", "mark", "next"]
        assert [e["tts"] for e in by_start] == sorted(
            e["tts"] for e in by_start)
    # A fresh thread's clock starts near zero; this one's did not.
    fresh = next(e for e in _spans(tracer, "parent")
                 if e["tid"] != threading.get_ident())
    assert fresh["tts"] < 50_000


def test_tracer_off_reads_no_clock(monkeypatch):
    """With the tracer off a span is the shared no-op and the thread's
    CPU clock is never read; no probe thread exists."""
    def boom():
        raise AssertionError("thread_time_ns read with the tracer off")

    monkeypatch.setattr(time, "thread_time_ns", boom)
    assert not trace.enabled()
    with trace.span("x", cat="c", k=1) as sp:
        assert sp is trace._NULL_SPAN
        sp.set(n=1)
        trace.annotate(k=2)
        trace.annotate_sum(native_us=1.0)
    trace.instant("nothing")
    assert trace.snapshot_events() == []
    assert "ctmr-gil-probe" not in {t.name for t in threading.enumerate()}
    # The patch bites: a live tracer does read it (on entry, once the
    # span is on the thread's stack: taken off again by hand).
    with pytest.raises(AssertionError):
        with trace.SpanTracer(ring_size=16).span("y"):
            pass
    assert [s._name for s in trace._ctx.stack] == ["y"]
    trace._ctx.stack.clear()


def test_annotate_sum_adds_up_under_the_innermost_span():
    tracer = trace._tracer = trace.SpanTracer(ring_size=16)
    with trace.span("outer"):
        with trace.span("inner"):
            trace.annotate_sum(native_us=2.0, gil_us=0.5)
            trace.annotate_sum(native_us=3.0, gil_us=0.25)
        trace.annotate_sum(native_us=1.0)
    trace.annotate_sum(native_us=7.0)  # no span open: nothing
    (inner,), (outer,) = _spans(tracer, "inner"), _spans(tracer, "outer")
    assert inner["args"] == {"native_us": 5.0, "gil_us": 0.75}
    assert outer["args"] == {"native_us": 1.0}


def test_record_span_hands_over_a_span_that_was_no_with_block():
    """A connection the query front serves in turns: its owner kept the
    clocks and the id, names the parent itself, and the event is a
    complete span like any other (the thread's name, the trace context,
    the process attributes), with ``tdur`` the CPU it was told and an
    id of the tracer's own where none was kept. The thread's stack of
    open spans is not touched."""
    tracer = trace._tracer = trace.SpanTracer(ring_size=16)
    t0 = tracer._t0_ns
    conn_id = tracer.next_id()
    with trace.span("around"):
        with trace.trace_context("ab" * 16, "cd" * 8):
            tracer.record_span("serve.wait", "serve", t0 + 2_000, t0 + 9_000,
                               parent=conn_id, lanes=2)
        tracer.record_span("front.conn", "front", t0 + 1_000, t0 + 11_000,
                           tts_ns=500_000, tdur_ns=3_000, span_id=conn_id,
                           requests=1)
    (wait,), (conn,) = _spans(tracer, "serve.wait"), _spans(tracer, "front.conn")
    (around,) = _spans(tracer, "around")
    assert (conn["id"], conn["parent"]) == (conn_id, 0)  # not "around"'s child
    assert (conn["ts"], conn["dur"], conn["tts"], conn["tdur"]) \
        == (1.0, 10.0, 500.0, 3.0)
    assert conn["cat"] == "front" and conn["args"] == {"requests": 1}
    assert wait["parent"] == conn_id and wait["id"] not in (0, conn_id)
    assert (wait["ts"], wait["dur"], wait["tdur"]) == (2.0, 7.0, 0.0)
    assert wait["args"] == {"lanes": 2, "trace_id": "ab" * 16,
                            "parent_id": "cd" * 8}
    assert wait["tid"] == conn["tid"] == around["tid"]
    assert around["parent"] == 0 and not trace._ctx.stack


def _probe_waits(seconds: float) -> list[float]:
    tracer = trace.enable(ring_size=4096)
    time.sleep(seconds)
    trace.disable()
    return [e["args"]["wait_us"] for e in _spans(tracer, "gil.probe")]


def _needs_probe():
    from ct_mapreduce_tpu import native

    lib = native.load()
    if lib is None or not lib.has_stamp:
        pytest.skip("no native library that stamps: no probe")


def test_the_probe_reads_the_gil_as_a_woken_thread_meets_it():
    """Beside one thread that never lets go, a woken thread waits out
    the interpreter's switch interval; beside none it does not. Order
    and a wide factor only."""
    _needs_probe()
    stop = threading.Event()

    def hog():
        while not stop.is_set():
            pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(0.02)
    spinner = threading.Thread(target=hog, name="hog", daemon=True)
    try:
        spinner.start()
        tracer = trace.enable(ring_size=4096)
        time.sleep(0.6)  # asleep: the hog alone wants the GIL
        trace.disable()
    finally:
        stop.set()
        spinner.join()
        sys.setswitchinterval(old)
    contended = [e["args"]["wait_us"] for e in _spans(tracer, "gil.probe")]
    idle = _probe_waits(0.4)
    assert len(contended) >= 5 and len(idle) >= 10
    assert statistics.median(contended) > 5_000
    assert statistics.median(idle) < statistics.median(contended) / 4
    # On a thread of its own, asleep under its span: CPU next to none.
    probes = _spans(tracer, "gil.probe")
    assert len({e["tid"] for e in probes}) == 1
    assert all(e["tid"] != threading.get_ident() for e in probes)
    assert all(e["dur"] >= 10_000 and e["parent"] == 0 for e in probes)
    names = {e["args"]["name"] for e in tracer.events() if e["ph"] == "M"}
    assert "ctmr-gil-probe" in names


def test_disable_joins_the_probe_and_a_bare_tracer_has_none():
    _needs_probe()

    def probes():
        return [t for t in threading.enumerate() if t.name == "ctmr-gil-probe"]

    bare = trace.SpanTracer(ring_size=16)
    with bare.span("x"):
        pass
    assert probes() == []
    trace.enable(ring_size=64)
    trace.enable(path="/tmp/whatever.json")  # the same tracer: one probe
    assert len(probes()) == 1
    trace.disable()
    assert probes() == []  # joined, not merely told
    # A tracer swapped out from under its probe (as fixtures do) ends it.
    trace.enable(ring_size=64)
    (thread,) = probes()
    trace._tracer = None
    thread.join(timeout=5)
    assert not thread.is_alive()
    trace.enable(ring_size=64)  # and the next enable starts a fresh one
    assert len(probes()) == 1


def test_no_probe_without_a_library_that_stamps(monkeypatch):
    from ct_mapreduce_tpu import native

    monkeypatch.setattr(native, "load", lambda: None)
    tracer = trace.enable(ring_size=64)
    with trace.span("works"):
        pass
    assert "ctmr-gil-probe" not in {t.name for t in threading.enumerate()}
    assert [e["name"] for e in _spans(tracer)] == ["works"]


def test_traceview_prints_cpu_off_core_and_who_holds_the_gil(tmp_path, capsys):
    def x(name, ts_ms, dur_ms, cpu_ms, ident, parent=0, tid=1, **args):
        return {"ph": "X", "name": name, "ts": ts_ms * 1e3,
                "dur": dur_ms * 1e3, "tts": 0.0, "tdur": cpu_ms * 1e3,
                "pid": 1, "tid": tid, "id": ident, "parent": parent,
                "args": args}

    events = [
        x("fetch.page", 0, 10, 4, 1),
        x("fetch.get_entries", 0, 6, 1, 2, parent=1),
        x("fetch.parse_json", 6, 2, 1, 3, parent=1, native_us=900.0,
          gil_us=1000.0),
        x("ingest.decode", 0, 20, 12, 4, tid=2, batch=7),
        x("decode.native_call", 2, 10, 9, 5, parent=4, tid=2, batch=7,
          threads=1, pad=2048, native_us=8000.0, gil_us=2000.0),
        x("ingest.submit", 20, 10, 1, 14, tid=2, batch=7),
        x("device.fold", 20, 10, 1, 6, parent=14, tid=2, batch=7),
        x("fold.metadata", 21, 2, 2, 7, parent=6, tid=2, batch=7,
          native_us=500.0, gil_us=250.0),
        x("serve.batch", 5, 8, 6, 8, tid=3),
        # The same ident, a connection thread's later: a dead thread's
        # ident is handed on, so families go by the outermost span.
        x("front.conn", 12, 4, 1, 9, tid=1, requests=1),
        x("serve.wait", 13, 2, 0, 10, parent=9, tid=1),
        x("serve.snapshot", 0, 3, 1, 11, tid=4),
        x("gil.probe", 0, 10, 0, 12, tid=5, wait_us=40.0),
        x("gil.probe", 10, 12, 0, 13, tid=5, wait_us=2000.0),
        {"ph": "X", "name": "old.span", "ts": 0.0, "dur": 1000.0, "pid": 1,
         "tid": 6},  # from a tracer older than tdur, ids and parents
    ]
    summary = traceview.stage_summary(events)
    assert summary["fetch.page"]["cpu_s"] == pytest.approx(0.004)
    assert summary["fetch.page"]["offcore_s"] == pytest.approx(0.006)
    assert summary["old.span"]["cpu_s"] == summary["old.span"]["offcore_s"] == 0
    fam = traceview.thread_families(events)
    assert set(fam) == {"downloader", "store", "batcher", "front", "refresh"}
    assert fam["downloader"] == pytest.approx({
        "roots": 1, "wall_s": 0.010, "cpu_s": 0.004, "offcore_s": 0.006,
        "native_s": 0.0009, "gil_s": 0.001})
    assert fam["store"] == pytest.approx({
        "roots": 2, "wall_s": 0.030, "cpu_s": 0.013, "offcore_s": 0.017,
        "native_s": 0.0085, "gil_s": 0.00225})
    assert fam["front"]["roots"] == 1 and fam["front"]["cpu_s"] \
        == pytest.approx(0.001)
    assert traceview.probe_waits(events) == [40.0, 2000.0]
    (row,) = traceview.batch_table(events)
    assert row["cpu:ingest.decode"] == pytest.approx(12.0)
    assert row["off:ingest.decode"] == pytest.approx(8.0)
    assert row["off:device.fold"] == pytest.approx(9.0)
    assert row["gil"] == pytest.approx(2.25)
    path = str(tmp_path / "t.json")
    with open(path, "w") as fh:
        json.dump({"traceEvents": events}, fh)
    assert traceview.main([path]) == 0
    out = capsys.readouterr().out
    assert "cpu_s" in out and "off_s" in out and "native_s" in out
    for family in fam:
        assert f"\n{family} " in out
    assert "gil.probe: 2 wake-ups, waited mean 1.020 ms, p95 2.000 ms" in out
    assert "\ngil.probe  " not in out  # asleep by design: no stage line
    assert traceview.main([path, "--batches"]) == 0
    out = capsys.readouterr().out
    assert "cpu:decode" in out and "off:fold" in out and " gil" in out


def test_traceview_cli(tmp_path, capsys):
    trace.enable(ring_size=256)
    for _ in range(3):
        with trace.span("stage.a"):
            time.sleep(0.002)
        with trace.span("stage.b"):
            pass
    path = str(tmp_path / "cli.json")
    trace.export(path)
    assert traceview.main([path]) == 0
    out = capsys.readouterr().out
    assert "stage.a" in out and "stage.b" in out
    assert "trace wall:" in out


def _x(name, ts_ms, dur_ms, **args):
    return {"ph": "X", "name": name, "ts": ts_ms * 1e3, "dur": dur_ms * 1e3,
            "pid": 1, "tid": 1, "args": args}


def test_traceview_split_by_what_overlaps(tmp_path, capsys):
    """``--split``: pages fetched while the store thread works against
    pages fetched while it waits, inside the batches asked for."""
    events = [
        _x("sink.accumulate", 9, 1, batch=1),  # cut at 10 ms
        _x("sink.accumulate", 99, 1, batch=2),  # cut at 100 ms
        _x("ingest.decode", 20, 10), _x("ingest.submit", 30, 20),
        _x("fetch.get_entries", 5, 2),    # before the window: left out
        _x("fetch.get_entries", 12, 2),   # clear
        _x("fetch.get_entries", 18, 4),   # half covered: covered
        _x("fetch.get_entries", 25, 6),   # spans decode and submit
        _x("fetch.get_entries", 49, 4),   # a quarter covered: clear
        _x("fetch.get_entries", 60, 2),   # clear
        _x("fetch.get_entries", 120, 50),  # after the window: left out
    ]
    window = traceview.cut_window(events, 1, 2)
    assert window == (10e3, 100e3)
    split = traceview.split_by_overlap(
        events, "fetch.get_entries", {"ingest.decode", "ingest.submit"},
        *window)
    assert split["covered"] == {"n": 2, "median_ms": 5.0, "mean_ms": 5.0,
                                "share": 0.75}
    assert split["clear"]["n"] == 3
    assert split["clear"]["median_ms"] == 2.0
    assert split["clear"]["share"] == pytest.approx(0.25 / 3)
    whole = traceview.split_by_overlap(events, "fetch.get_entries",
                                       {"ingest.decode"})
    assert (whole["covered"]["n"], whole["clear"]["n"]) == (2, 5)
    path = str(tmp_path / "split.json")
    with open(path, "w") as fh:
        json.dump({"traceEvents": events}, fh)
    assert traceview.main([path, "--split", "fetch.get_entries", "--against",
                           "ingest.decode,ingest.submit",
                           "--between", "1,2"]) == 0
    out = capsys.readouterr().out
    assert "covered      2  median 5.000 ms" in out
    assert traceview.main([path, "--split", "no.such", "--against", "x"]) == 1
    with pytest.raises(ValueError):
        traceview.cut_window(events, 1, 3)
