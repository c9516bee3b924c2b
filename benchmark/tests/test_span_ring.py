"""Tests of ``readers/span_ring.py``, the reader of the program's own
span ring, and of the nine per-layer metrics that read it:
python3 -m pytest benchmark/tests -q
"""

from __future__ import annotations

import gzip
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import layers  # noqa: E402
from layers import ABSENT  # noqa: E402
from readers import span_ring  # noqa: E402

RING_METRICS = (
    "fetch.blocked_share", "fetch.http_us_per_entry",
    "fetch.parse_us_per_entry", "sink.starved_share", "decode.native_share",
    "ckpt.save_s", "ckpt.d2h_s", "ckpt.write_s", "host.unattributed_share")

THREADS = [{"ph": "M", "name": "thread_name", "tid": 1,
            "args": {"name": "sync-http://log"}},
           {"ph": "M", "name": "thread_name", "tid": 2,
            "args": {"name": "store-0"}},
           {"ph": "M", "name": "thread_name", "tid": 3,
            "args": {"name": "MainThread"}}]


def span(name, start_s, dur_s, ident, parent=0, tid=1, **args):
    ev = {"ph": "X", "name": name, "ts": start_s * 1e6, "dur": dur_s * 1e6,
          "tid": tid, "id": ident, "parent": parent}
    if args:
        ev["args"] = args
    return ev


def ctx_of(events, dropped=0, **out):
    stamps = {"t_first": 10.0, "t_folded": 20.0, "t_round_folded": 22.0,
              "t_durable": 30.0}
    stamps.update(out)
    return {"ring": {"events": THREADS + events, "mono_t0": 0.0,
                     "dropped": dropped},
            "out": stamps, "entries": 1000, "batches": 4}


# Three pages on the downloader, one decode on the store thread, two
# saves after the round; times in seconds on the run's clock.
EVENTS = [
    span("fetch.page", 9.0, 2.0, 1),                    # straddles t_first
    span("fetch.get_entries", 9.0, 0.5, 2, parent=1),   # before the window
    span("fetch.enqueue", 9.5, 1.5, 3, parent=1),       # 1.0 s inside
    span("fetch.page", 12.0, 4.0, 4),
    span("fetch.get_entries", 12.0, 1.0, 5, parent=4),
    span("fetch.enqueue", 13.5, 2.5, 6, parent=4),
    span("fetch.page", 19.0, 3.0, 7),                   # straddles t_folded
    span("fetch.enqueue", 19.5, 2.5, 8, parent=7),      # 0.5 s inside
    span("ingest.decode", 11.0, 4.0, 9, tid=2, batch=1),
    span("native.decode_batch", 11.5, 3.0, 10, parent=9, tid=2, batch=1),
    span("decode.native_call", 12.0, 1.0, 11, parent=10, tid=2, batch=1),
    span("sink.queue_wait", 15.0, 5.0, 12, tid=2),
    span("fetch.page", 14.0, 1.0, 13, tid=3),           # another thread's
    span("ckpt.save", 22.5, 6.0, 14, kind="full"),
    span("ckpt.d2h", 22.5, 1.0, 15, parent=14),
    span("ckpt.write", 23.5, 4.5, 16, parent=14),
    span("ckpt.save", 29.0, 0.25, 17, tid=3, kind="noop"),
    span("ckpt.save", 5.0, 2.0, 18, kind="full"),       # the warm-up's
]


def read(params, ctx=None):
    return span_ring.read(params, ctx or ctx_of(EVENTS))


def test_spans_are_clipped_to_the_window():
    assert read({"span": "fetch.enqueue"}) == pytest.approx(1.0 + 2.5 + 0.5)
    assert read({"span": "fetch.enqueue", "per": "window_seconds",
                 "scale": 100.0}) == pytest.approx(40.0)
    assert read({"span": "fetch.get_entries", "per": "entry",
                 "scale": 1e6}) == pytest.approx(1000.0)
    assert read({"span": "sink.queue_wait", "per": "batch"}) \
        == pytest.approx(5.0 / 4)


def test_self_time_takes_the_children_off_by_parent():
    # Three pages on the downloader and one on another thread, clipped:
    # 1.0 + 4.0 + 1.0 + 1.0 = 7.0; their children inside the window:
    # enqueue 4.0 and get_entries 1.0. Another thread's span of the
    # same name takes nothing off and loses nothing.
    assert read({"span": "fetch.page"}) == pytest.approx(7.0)
    assert read({"span": "fetch.page", "self": True}) == pytest.approx(2.0)
    assert read({"span": "native.decode_batch", "self": True}) \
        == pytest.approx(2.0)


def test_one_span_over_another():
    assert read({"span": "decode.native_call", "per": "span:ingest.decode",
                 "scale": 100.0}) == pytest.approx(25.0)
    # The base's family is nowhere in the run: not in this program.
    assert read({"span": "decode.native_call",
                 "per": "span:no.such.span"}) is ABSENT
    # Its family is there and the name is not: a span renamed.
    assert read({"span": "decode.native_call",
                 "per": "span:ingest.no_such_span"}) is None


def test_args_filter_and_the_drain_phase():
    full = {"span": "ckpt.save", "args": {"kind": "full"}, "phase": "drain"}
    assert read(full) == pytest.approx(6.0)  # not the warm-up round's
    assert read({"span": "ckpt.save", "phase": "drain"}) \
        == pytest.approx(6.25)
    assert read({"span": "ckpt.write", "phase": "drain"}) \
        == pytest.approx(4.5)
    assert read({"span": "ckpt.save", "args": {"kind": "segment"},
                 "phase": "drain"}) is None
    # In the window there is no save at all.
    assert read({"span": "ckpt.save"}) is None


def test_uncovered_is_the_worst_of_the_named_threads():
    # Downloader: pages cover [10,11] [12,16] [19,20] = 6 of 10 s.
    # Store thread: decode [11,15], wait [15,20] = 9 of 10 s. The main
    # thread's spans do not count.
    params = {"uncovered": ["sync-", "store-"], "per": "window_seconds",
              "scale": 100.0}
    assert read(params) == pytest.approx(40.0)
    assert read(dict(params, uncovered=["store-"])) == pytest.approx(10.0)
    assert read(dict(params, uncovered=["nobody-"])) is None


def test_a_renamed_span_is_nothing_to_read_and_an_older_program_is_absent():
    # The family is in the run, the name is not: a span renamed.
    assert read({"span": "fetch.between_pages"}) is None
    # No span of the family anywhere in the run: not in this program.
    assert read({"span": "serve.wait"}) is ABSENT
    # ... unless the ring forgot events: then nobody can tell.
    assert read({"span": "serve.wait"}, ctx_of(EVENTS, dropped=7)) is None
    # A tracer that records no parents (the program before these spans).
    old = [{k: v for k, v in e.items() if k not in ("id", "parent")}
           for e in EVENTS]
    assert read({"span": "fetch.enqueue"}, ctx_of(old)) is ABSENT
    with pytest.raises(ValueError):
        read({"span": "fetch.enqueue", "phase": "warmup"})


def test_an_absent_metric_is_left_out_and_an_empty_one_fails_by_name():
    """``layers.read_metrics`` on ``BENCHMARK.json``'s own entries: a
    program without the ``fetch.`` family leaves ``fetch.blocked_share``
    out and names it; one that has the family and no ``fetch.enqueue`` in
    the window fails the run by the metric's name."""
    entries = [{"name": "fetch.blocked_share", "unit": "%",
                "workloads": ["backfill-1log"]},
               {"name": "sink.starved_share", "unit": "%",
                "workloads": ["backfill-1log"]}]
    no_fetch = [e for e in EVENTS if not e["name"].startswith("fetch.")]
    metrics, absent = layers.read_metrics(entries, "backfill-1log",
                                          ctx_of(no_fetch))
    assert absent == ["fetch.blocked_share"]
    assert metrics == {"sink.starved_share": {
        "value": pytest.approx(50.0), "unit": "%"}}
    assert layers.read_metrics(entries, "some-other-cell",
                               ctx_of(no_fetch)) == ({}, [])
    none_in_window = [e for e in EVENTS if e["name"] != "fetch.enqueue"]
    with pytest.raises(harness.RunFailed, match="fetch.blocked_share"):
        layers.read_metrics(entries, "backfill-1log", ctx_of(none_in_window))
    # The rehearsal's way (strict off) leaves both kinds out.
    assert layers.read_metrics(entries[:1], "backfill-1log",
                               ctx_of(none_in_window), strict=False) \
        == ({}, [])


def test_nothing_to_read_from_a_window_the_ring_did_not_see_whole():
    later = [e for e in EVENTS if e["ts"] >= 12e6]
    # Dropped, and what is left starts inside the window: refuse.
    assert read({"span": "fetch.enqueue"}, ctx_of(later, dropped=7)) is None
    # Dropped, but the oldest event left ended before the window
    # opened: everything inside it is there.
    assert read({"span": "fetch.enqueue"}, ctx_of(EVENTS, dropped=7)) \
        == pytest.approx(4.0)
    assert read({"span": "ckpt.write", "phase": "drain"},
                ctx_of(later, dropped=7)) == pytest.approx(4.5)


def recorded():
    with gzip.open(os.path.join(HERE, "data", "recorded_ring.json.gz"),
                   "rt") as fh:
        doc = json.load(fh)
    return {"ring": doc["ring"], "out": doc["out"],
            "entries": doc["entries"], "batches": doc["batches"]}


def ring_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        listed = {m["name"]: m for m in json.load(fh)["per_layer"]}
    return [listed[name] for name in RING_METRICS]


def test_ring_metrics_on_the_recorded_ring():
    """The ring of a tiny CPU rehearsal (six 1,024-entry batches in the
    window, 64-entry pages), trimmed to the window and the drain. The
    numbers were read off it once and must not move; two of them are
    recounted here the slow way."""
    ctx = recorded()
    got = {}
    for entry in ring_metrics():
        with open(os.path.join(BENCH, "layers",
                               entry["name"] + ".json")) as fh:
            spec = json.load(fh)
        assert spec["reader"] == "span_ring"
        got[entry["name"]] = span_ring.read(spec["params"], ctx)
    assert got == pytest.approx({
        "fetch.blocked_share": 37.4873, "fetch.http_us_per_entry": 21.95337,
        "fetch.parse_us_per_entry": 7.196617, "sink.starved_share": 0.3187796,
        "decode.native_share": 53.53614, "ckpt.save_s": 0.04042468,
        "ckpt.d2h_s": 0.000315822, "ckpt.write_s": 0.03729472,
        "host.unattributed_share": 1.434204}, rel=1e-5)
    ring, out = ctx["ring"], ctx["out"]
    lo, hi = out["t_first"], out["t_folded"]
    blocked = 0.0
    for e in ring["events"]:
        if e.get("name") == "fetch.enqueue":
            a = ring["mono_t0"] + e["ts"] / 1e6
            blocked += max(0.0, min(a + e["dur"] / 1e6, hi) - max(a, lo))
    assert got["fetch.blocked_share"] == pytest.approx(
        100.0 * blocked / (hi - lo))
    saves = [e for e in ring["events"] if e.get("name") == "ckpt.save"]
    assert [e["args"]["kind"] for e in saves] == ["full", "noop"]
    assert got["ckpt.save_s"] == pytest.approx(saves[0]["dur"] / 1e6)
    assert got["ckpt.d2h_s"] + got["ckpt.write_s"] <= got["ckpt.save_s"]


def test_ring_metrics_are_listed_after_the_thirteen():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    assert len(bench["per_layer"]) == 22
    assert [m["name"] for m in bench["per_layer"][13:]] == list(RING_METRICS)
    for m in ring_metrics():
        assert m["source"] == "program_span"
        assert m["workloads"] == ["backfill-1log"]
        with open(os.path.join(BENCH, "layers", m["name"] + ".json")) as fh:
            assert json.load(fh)["reader"] == "span_ring"


def test_rehearsal_prints_the_ring_metrics():
    """The traced run end to end on the CPU: every one of the nine has
    something to read, and the two fetch spans divide the old fetch
    timer."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"), "1",
         "31342", "trace"], capture_output=True, text=True, timeout=600,
        env=env, cwd=ROOT)
    lines = [json.loads(x) for x in res.stdout.strip().splitlines()]
    assert lines[-1]["correct"] is True, res.stderr[-2000:]
    assert not any("absent" in x for x in lines if isinstance(x, dict))
    metrics = next(x for x in lines if isinstance(x, list))[0]
    for m in ring_metrics():
        assert metrics[m["name"]]["value"] >= 0, m["name"]
        assert metrics[m["name"]]["unit"] == m["unit"]
    for name in ("fetch.blocked_share", "sink.starved_share",
                 "host.unattributed_share", "decode.native_share"):
        assert metrics[name]["value"] <= 100.0, name
    parts = (metrics["fetch.http_us_per_entry"]["value"]
             + metrics["fetch.parse_us_per_entry"]["value"])
    assert parts == pytest.approx(metrics["fetch.us_per_entry"]["value"],
                                  rel=0.25)
    assert metrics["ckpt.save_s"]["value"] <= metrics["ckpt.drain_s"]["value"]
