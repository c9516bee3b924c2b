"""Span tracer (telemetry/trace.py): Chrome trace-event JSON schema,
span nesting, ring bound, the disabled fast path, and the overlap
pipeline's per-stage spans summarized by tools/traceview.py."""

import json
import os
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
        elif e["ph"] == "i":
            assert isinstance(e["ts"], (int, float))
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
    # 4 workers x 3 spans, 4 instants, >= 4 thread-name records.
    xs = [e for e in events if e["ph"] == "X"]
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
    assert trace.now_us() == 0.0


def test_ring_bound():
    """The event ring is bounded (a week-long run cannot grow without
    limit) and keeps the newest window."""
    trace.enable(ring_size=32)
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


class _FakeSink:
    """Stage stand-in with the duck-typed surface the overlap scheduler
    drives, each stage sleeping so spans have real extent."""

    def __init__(self):
        self._dispatch_lock = threading.Lock()
        self.completed = []

    def _prepare_chunk(self, pairs):
        time.sleep(0.02)
        return pairs

    def _submit_chunk(self, prep):
        time.sleep(0.01)
        return [("pending", prep, None)]

    def _complete_item(self, payload, der_of):
        time.sleep(0.015)
        self.completed.append(payload)

    def _store_pems(self, payload, der_of):
        pass


def test_overlap_pipeline_stage_spans_and_traceview(tmp_path):
    """The pipeline's decode/submit/drain spans land in the trace, and
    tools/traceview.py summarizes them into per-stage occupancy that
    shows the stages actually overlapping (busy sum > wall)."""
    from ct_mapreduce_tpu.ingest.overlap import OverlapIngestPipeline

    trace.enable(ring_size=4096)
    sink = _FakeSink()
    pipe = OverlapIngestPipeline(sink, decode_workers=2, queue_depth=2)
    n_chunks = 6
    for i in range(n_chunks):
        pipe.submit_chunk([("li", "ed")] * 4)
    pipe.drain_all()
    pipe.close()
    assert len(sink.completed) == n_chunks

    path = str(tmp_path / "overlap.json")
    trace.export(path)
    events = traceview.load(path)
    _validate_schema(events)
    _validate_nesting(events)
    summary = traceview.stage_summary(
        events, stages=("ingest.decode", "ingest.submit", "ingest.drain"))
    wall = summary.pop("_wall_s")
    assert set(summary) == {"ingest.decode", "ingest.submit",
                            "ingest.drain"}
    busy = 0.0
    for name, s in summary.items():
        assert s["count"] == n_chunks, (name, s)
        assert s["busy_s"] > 0
        busy += s["busy_s"]
    # Two decode workers ran ahead of submit/drain: total stage busy
    # exceeds the wall clock — the overlap, read straight off the
    # trace (the serialized sum here is ~0.045s x 6 vs ~0.02s x 3 + e).
    assert busy > wall * 1.05, (busy, wall)
    # The submit span nests inside the submit_locked envelope.
    locked = traceview.stage_summary(events,
                                     stages=("ingest.submit_locked",))
    assert locked["ingest.submit_locked"]["count"] == n_chunks
    assert (locked["ingest.submit_locked"]["busy_s"]
            >= summary["ingest.submit"]["busy_s"] * 0.9)


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
