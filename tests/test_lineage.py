"""Batch lineage (telemetry/trace.py ids, parents and the shared
``batch``; the ``fetch.`` / ``sink.`` / ``decode.`` / ``fold.`` /
``ckpt.`` spans): a tiny ct-fetch-shaped run over a fake transport
yields, for every batch, the whole tree from its get-entries pages to
its fold, and the checkpoint's phases; with the tracer off nothing is
recorded and the always-on samples still reach the metrics sink.

Fixtures are hand-assembled DER (``utils/minicert``): the ingest path
parses and never verifies.
"""

import base64
import datetime
import json
import threading
from collections import defaultdict

import pytest

from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
from ct_mapreduce_tpu.ingest import leaf as leaflib
from ct_mapreduce_tpu.ingest.sync import AggregatorSink, LogSyncEngine
from ct_mapreduce_tpu.native import available, leafpack
from ct_mapreduce_tpu.storage.certdb import FilesystemDatabase
from ct_mapreduce_tpu.storage.mockbackend import MockBackend
from ct_mapreduce_tpu.storage.mockcache import MockRemoteCache
from ct_mapreduce_tpu.telemetry import metrics, trace
from ct_mapreduce_tpu.utils import minicert
from tests.fakelog import FakeLog

NOW = datetime.datetime(2025, 1, 1, tzinfo=datetime.timezone.utc)
LOG = "ct.example.com/fake"  # FakeLog's short URL
PAGE, BATCH, ENTRIES = 8, 16, 48
ISSUER = minicert.make_cert(serial=1, issuer_cn="Lineage CA", is_ca=True)


@pytest.fixture(autouse=True)
def clean_telemetry():
    trace.disable()
    metrics.set_sink(metrics.InMemSink())
    yield
    trace.disable()
    metrics.set_sink(metrics.InMemSink())


def fake_log() -> FakeLog:
    log = FakeLog()
    log.max_batch = PAGE
    for j in range(ENTRIES):
        leaf = minicert.make_cert(serial=100 + j, issuer_cn="Lineage CA",
                                  subject_cn="l.example", is_ca=False)
        log.entries.append({
            "leaf_input": base64.b64encode(
                leaflib.encode_leaf_input(leaf, 1000 + j)).decode(),
            "extra_data": base64.b64encode(
                leaflib.encode_extra_data([ISSUER])).decode()})
    return log


def run_fetch(tmp_path):
    """The engine as ct-fetch wires it in TPU mode: raw batches, one
    store thread, the checkpoint hook before each cursor save, and the
    round's own save at the end."""
    log = fake_log()
    agg = TpuAggregator(capacity=1 << 12, batch_size=BATCH, now=NOW)
    sink = AggregatorSink(agg, flush_size=BATCH)
    path = str(tmp_path / "agg.npz")
    engine = LogSyncEngine(
        sink, FilesystemDatabase(MockBackend(), MockRemoteCache()),
        num_threads=1, raw_batches=True,
        checkpoint_hook=lambda: sink.checkpointed_save(
            lambda: agg.save_checkpoint(path)))
    engine.start_store_threads()
    engine.sync_log(log.url, transport=log.transport)
    engine.wait_for_downloads(timeout=120)
    engine.stop()
    agg.save_checkpoint(path)
    sink.close()
    assert not engine.errors, engine.errors
    assert agg.drain().total == ENTRIES
    return agg


def spans():
    return [e for e in trace.snapshot_events() if e["ph"] == "X"]


def by_batch(events):
    out = defaultdict(lambda: defaultdict(list))
    for e in events:
        if "batch" in e.get("args", {}):
            out[e["args"]["batch"]][e["name"]].append(e)
    return out


# The spans one batch causes, downstream of the cut.
CHAIN = ("sink.accumulate", "ingest.decode", "native.decode_batch",
         "decode.pack", "ingest.submit_locked", "ingest.submit",
         "device.step", "device.readback", "device.fold",
         "fold.wait_device")
NATIVE = ("decode.concat_b64", "decode.native_call")


def test_every_batch_has_its_whole_lineage(tmp_path):
    """Pages -> cut -> decode -> native call -> submit -> step ->
    readback/fold, the same ``batch`` on every span."""
    trace.enable()
    run_fetch(tmp_path)
    events = spans()
    batches = by_batch(events)
    assert sorted(batches) == [1, 2, 3]
    pages = [e for e in events if e["name"] == "fetch.page"]
    assert sorted(p["args"]["start"] for p in pages) \
        == list(range(0, ENTRIES, PAGE))
    want = CHAIN + (NATIVE if leafpack.load_native() is not None else ())
    for n, names in batches.items():
        for name in want:
            assert len(names[name]) == 1, (n, name, sorted(names))
        cut = names["sink.accumulate"][0]["args"]
        first, last = (n - 1) * BATCH, n * BATCH - 1
        assert cut["pages"] == [[LOG, first, last]]
        # The pages join to the batch by the ranges the cut recorded.
        mine = [p["args"] for p in pages if p["args"]["log"] == LOG
                and first <= p["args"]["start"] <= last]
        assert sum(p["n"] for p in mine) == BATCH
        assert names["ingest.decode"][0]["args"]["entries"] == BATCH


def test_parents_form_a_tree_per_thread(tmp_path):
    trace.enable()
    run_fetch(tmp_path)
    events = [e for e in trace.snapshot_events() if e["ph"] in "Xi"]
    by_id = {e["id"]: e for e in events}
    assert len(by_id) == len(events) and 0 not in by_id
    for e in events:
        if not e["parent"]:
            continue
        up = by_id[e["parent"]]
        assert up["tid"] == e["tid"] and up["ph"] == "X", (up, e)
        assert up["ts"] <= e["ts"]
        assert e["ts"] + e.get("dur", 0) <= up["ts"] + up["dur"] + 1e-3
    child_of = {(by_id[e["parent"]]["name"], e["name"])
                for e in events if e["parent"]}
    assert {("fetch.page", "fetch.get_entries"),
            ("fetch.page", "fetch.parse_json"),
            ("fetch.page", "fetch.enqueue"),
            ("ingest.decode", "native.decode_batch"),
            ("ingest.decode", "decode.pack"),
            ("ingest.submit_locked", "ingest.submit"),
            ("ingest.submit", "device.step"),
            ("device.readback", "device.fold"),
            ("device.fold", "fold.wait_device"),
            # The exit save is the round's (PR 36): the checkpoint is
            # its child, and so is each cursor write it is followed by.
            ("round.cursor_wait", "round.save"),
            ("round.save", "ckpt.wait_outstanding"),
            ("round.save", "ckpt.save"),
            ("round.save", "fetch.save_cursor")} <= child_of
    roots = {e["name"] for e in events if not e["parent"]}
    assert {"fetch.page", "round.cursor_wait", "sink.queue_wait",
            "sink.accumulate", "ingest.decode", "ingest.submit_locked",
            "ckpt.save"} <= roots


def test_page_spans_say_what_was_fetched(tmp_path):
    trace.enable()
    run_fetch(tmp_path)
    events = spans()
    for page in (e for e in events if e["name"] == "fetch.page"):
        assert page["args"]["log"] == LOG and page["args"]["n"] == PAGE
        kids = [e for e in events if e["parent"] == page["id"]]
        got = [e for e in kids if e["name"] == "fetch.get_entries"]
        assert len(got) == 1 and got[0]["args"]["attempts"] == 1
        assert got[0]["args"]["bytes"] > PAGE * 100
        parsed = [{k: v for k, v in e["args"].items()
                   if k not in ("native_us", "gil_us")}
                  for e in kids if e["name"] == "fetch.parse_json"]
        assert parsed == [{"n": PAGE, "scanned": int(available())}]
        put = [e for e in kids if e["name"] == "fetch.enqueue"]
        assert len(put) == 1 and put[0]["args"]["depth"] >= 0
        assert sum(e["dur"] for e in kids) <= page["dur"] + 1e-3
    # One queue wait per item the store thread took (and its sentinel).
    waits = [e for e in events if e["name"] == "sink.queue_wait"]
    assert len(waits) == ENTRIES // PAGE + 1


def test_checkpoint_save_has_its_three_phases(tmp_path):
    """The cursor save's checkpoint is a full base (a count-only sink
    cannot extend a chain): d2h, write and seal are its children and
    fit inside it; the round's own save after it has nothing to write.
    The save the round's end caused carries its reason, ``exit``, and
    the cursor is written after it, both under ``round.save``."""
    trace.enable()
    agg = run_fetch(tmp_path)
    events = spans()
    saves = [e for e in events if e["name"] == "ckpt.save"]
    assert [s["args"]["kind"] for s in saves] == ["full", "noop"]
    full, noop = saves
    kids = {e["name"]: e for e in events if e["parent"] == full["id"]}
    assert set(kids) == {"ckpt.d2h", "ckpt.write", "ckpt.seal"}
    assert sum(k["dur"] for k in kids.values()) <= full["dur"]
    assert full["args"]["bytes_in"] == agg.table.rows.nbytes
    # Since PR 42 the base is packed: what crosses is the occupied
    # slots' 20 B each and a byte a bucket, not the table.
    d2h = kids["ckpt.d2h"]["args"]
    assert d2h["bytes"] == d2h["occupied"] * 20 + agg.table.rows.shape[0]
    assert (d2h["occupied"], d2h["capacity"]) \
        == (int(agg.table.count), agg.capacity)
    assert kids["ckpt.write"]["args"]["bytes"] == full["args"]["bytes_out"] \
        == (tmp_path / "agg.npz").stat().st_size
    cursor = next(e for e in events if e["name"] == "fetch.save_cursor")
    assert cursor["args"] == {"log": LOG, "position": ENTRIES,
                              "reason": "exit"}
    round_save = next(e for e in events if e["name"] == "round.save")
    assert round_save["args"] == {"reason": "exit", "logs": 1, "cursors": 1,
                                  "entries": ENTRIES}
    assert full["parent"] == cursor["parent"] == round_save["id"]
    assert full["ts"] + full["dur"] <= cursor["ts"]  # checkpoint, then cursor
    assert full["args"]["reason"] == "exit" and "reason" not in noop["args"]
    assert not [e for e in events if e["parent"] == noop["id"]]


class Recorder:
    """A fan-out emitter that keeps what it is given, in order."""

    def __init__(self):
        self.seen = []

    def add_sample(self, key, value):
        self.seen.append(("sample", key, value))

    def incr_counter(self, key, value):
        self.seen.append(("counter", key, value))

    def set_gauge(self, key, value):
        pass


def test_tracer_off_records_nothing_and_the_samples_still_flow(tmp_path):
    assert not trace.enabled()
    noop = trace.span("fetch.page", cat="fetch", log=LOG)
    assert noop is trace.span("ckpt.save") and noop.set(n=1) is noop
    run_fetch(tmp_path)
    assert trace.snapshot_events() == [] and trace.get_tracer() is None
    snap = metrics.get_sink().snapshot()
    pages = ENTRIES // PAGE
    assert snap["samples"][f"LogWorker.{LOG}.submitToChannel"]["count"] \
        == pages
    assert snap["samples"]["ct-fetch.queueWait"]["count"] == pages + 1
    assert snap["samples"]["ckpt.save"]["count"] == 2
    assert snap["counters"]["ckpt.bytes_written"] \
        == (tmp_path / "agg.npz").stat().st_size
    assert snap["counters"]["ct-fetch.foldedEntries"] == ENTRIES
    assert "ingest.decode_ns_per_entry" not in snap["samples"]


def test_folded_entries_follow_each_complete_batch_in_order(tmp_path):
    """The contract the benchmark's window rests on: one completeBatch
    sample per folded batch, the batch's lanes counted right after."""
    rec = Recorder()
    metrics.set_sink(metrics.InMemSink(), rec)
    run_fetch(tmp_path)
    keys = ("ct-fetch.completeBatch", "ct-fetch.foldedEntries")
    seq = [(kind, key, value) for kind, key, value in rec.seen
           if key in keys]
    assert len(seq) == 2 * (ENTRIES // BATCH)
    for (k0, key0, _), (k1, key1, lanes) in zip(seq[::2], seq[1::2]):
        assert (k0, key0) == ("sample", keys[0])
        assert (k1, key1, lanes) == ("counter", keys[1], float(BATCH))


def test_identity_arguments_pass_down_the_stack():
    trace.enable()
    seen = {}

    def other_thread():
        with trace.span("t.other") as sp:
            seen["other"] = dict(sp._args)

    with trace.span("t.root", batch=7, reason="exit", k=1) as root:
        with trace.span("t.child") as child:
            with trace.span("t.grandchild", batch=9):
                trace.instant("t.mark")
        t = threading.Thread(target=other_thread)
        t.start()
        t.join()
        root.set(late=True)
        with trace.span("t.after"):
            pass
    ev = {e["name"]: e for e in trace.snapshot_events() if e["ph"] != "M"}
    assert ev["t.child"]["args"] == {"batch": 7, "reason": "exit"}
    assert ev["t.grandchild"]["args"] == {"batch": 9, "reason": "exit"}
    assert ev["t.after"]["args"] == {"batch": 7, "reason": "exit"}
    assert ev["t.root"]["args"] == {"batch": 7, "reason": "exit", "k": 1,
                                    "late": True}
    assert seen["other"] == {} and ev["t.other"]["parent"] == 0
    assert ev["t.mark"]["parent"] == ev["t.grandchild"]["id"]
    assert ev["t.grandchild"]["parent"] == child.id == ev["t.child"]["id"]
    assert ev["t.child"]["parent"] == ev["t.root"]["id"]


def test_annotate_reaches_the_innermost_open_span_only():
    """``trace.annotate``: the callee names what the caller's span
    should say (``serve.batch`` learns the epoch that answered it);
    nothing where no span is open, on another thread, or with the
    tracer off."""
    trace.annotate(lost=1)  # tracer off, no span: nothing, no error
    trace.enable()
    trace.annotate(lost=2)

    def other_thread():
        trace.annotate(lost=3)

    with trace.span("t.outer", k=1):
        with trace.span("t.inner"):
            trace.annotate(epoch=5, age_ms=1.5)
            t = threading.Thread(target=other_thread)
            t.start()
            t.join()
        with trace.span("t.sibling"):
            pass
    ev = {e["name"]: e for e in trace.snapshot_events() if e["ph"] != "M"}
    assert ev["t.inner"]["args"] == {"epoch": 5, "age_ms": 1.5}
    assert ev["t.outer"]["args"] == {"k": 1}
    assert "args" not in ev["t.sibling"]


def test_a_reused_thread_ident_keeps_every_name():
    """The OS hands a dead thread's ident to the next thread: each
    still gets its own thread_name record."""
    trace.enable()
    for k in range(4):
        t = threading.Thread(target=lambda: trace.instant("m"),
                             name=f"one-after-another-{k}")
        t.start()
        t.join(timeout=30)
    names = {e["args"]["name"] for e in trace.snapshot_events()
             if e["ph"] == "M"}
    assert {f"one-after-another-{k}" for k in range(4)} <= names


def test_the_ring_counts_what_it_drops(tmp_path):
    # Installed bare: the module's enable() starts the GIL probe, whose
    # spans would be counted among these.
    tracer = trace._tracer = trace.SpanTracer(ring_size=32)
    assert tracer.dropped() == 0
    for i in range(200):
        with trace.span("s", i=i):
            pass
    assert tracer.dropped() == 168 and len(spans()) == 32
    # From several threads at once, still exact.
    def burst():
        for _ in range(50):
            trace.instant("m")

    threads = [threading.Thread(target=burst) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert tracer.dropped() == 368
    path = trace.export(str(tmp_path / "ring.json"))
    with open(path) as fh:
        assert json.load(fh)["otherData"]["dropped"] == 368
    tracer.clear()
    assert tracer.dropped() == 0 and spans() == []


def test_trace_annotation_is_given_the_scalar_arguments(monkeypatch):
    import jax.profiler

    calls = []

    class Annotation:
        def __init__(self, name, **kwargs):
            calls.append((name, kwargs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    trace.enable(jax_annotations=True)
    with trace.span("ingest.decode", cat="ingest", batch=3, entries=16):
        with trace.span("sink.accumulate", pages=[[LOG, 0, 15]], n=8,
                        log=LOG, odd="a=b,c") as sp:
            sp.set(late=1)
    # (the GIL probe's spans are mirrored like any other)
    assert [c for c in calls if c[0] != "gil.probe"] == [
        ("ingest.decode", {"batch": 3, "entries": 16}),
        ("sink.accumulate", {"n": 8, "log": LOG, "batch": 3})]


def test_the_profile_keeps_the_bare_span_name(tmp_path):
    """With arguments mirrored into the profiler, the event's name in
    the profile is still the span's: the benchmark's reduction matches
    on it."""
    import glob

    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    trace.enable(jax_annotations=True)
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with trace.span("native.decode_batch", cat="native", entries=16,
                        pad=1024, batch=5):
            pass
    finally:
        jax.profiler.stop_trace()
    (xplane,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(xplane)
    found = [(e.name, dict(e.stats)) for plane in data.planes
             for line in plane.lines for e in line.events
             if e.name.startswith("native.")]
    assert found == [("native.decode_batch",
                      {"entries": 16, "pad": 1024, "batch": 5})]
