"""Tests of ``readers/span_cpu.py``, the reader of what a span says
beside its wall seconds (its thread's CPU time, its time off a core, a
numeric argument), and of the seven per-layer metrics of the layer
"host threads (the GIL)":  python3 -m pytest benchmark/tests -q
"""

from __future__ import annotations

import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import layers  # noqa: E402
import listing  # noqa: E402
from layers import ABSENT  # noqa: E402
from readers import span_cpu, span_mean  # noqa: E402
from test_span_ring import ctx_of, span  # noqa: E402

ALL_CELLS = ["backfill-1log", "backfill-1log-query", "backfill-3log"]
# In BENCHMARK.json's order; the first five list every cell.
GIL_METRICS = (
    "gil.acquire_ms", "gil.acquire_p95_ms", "decode.gil_wait_ms_per_batch",
    "fetch.cpu_us_per_entry", "fetch.offcore_us_per_entry",
    "serve.batch_cpu_ms", "front.cpu_ms_per_request")
READERS = {"span_cpu": span_cpu, "span_mean": span_mean}


def cpu(ev: dict, tdur_s: float) -> dict:
    """``ev`` with its thread's CPU seconds, as the tracer writes them."""
    return dict(ev, tts=0.0, tdur=tdur_s * 1e6)


# A window of ten seconds (10 to 20 on the run's clock; 1,000 entries,
# 4 batches): two pages of one downloader and one of another, two
# decodes, two batches of queries, three connections, twenty wake-ups
# of the probe. Seconds; arguments in microseconds as the program's are.
EVENTS = [
    cpu(span("fetch.page", 11.0, 2.0, 1), 0.5),
    cpu(span("fetch.get_entries", 11.0, 1.0, 2, parent=1), 0.25),
    cpu(span("fetch.page", 14.0, 4.0, 3), 1.0),
    cpu(span("fetch.get_entries", 14.0, 3.0, 4, parent=3), 0.5),
    cpu(span("fetch.page", 15.0, 1.0, 5, tid=3), 0.5),
    cpu(span("fetch.get_entries", 15.0, 0.5, 6, parent=5, tid=3), 0.25),
    cpu(span("fetch.page", 19.5, 2.0, 7), 0.5),              # ends after
    cpu(span("fetch.page", 8.0, 1.5, 8), 1.5),               # ended before
    cpu(span("decode.concat_b64", 12.0, 0.5, 9, tid=2), 0.125),
    cpu(span("decode.native_call", 12.5, 1.0, 10, tid=2, threads=1, pad=2048,
             native_us=900_000.0, gil_us=50_000.0), 0.9),
    cpu(span("decode.concat_b64", 16.0, 0.25, 11, tid=2), 0.125),
    cpu(span("decode.native_call", 16.25, 1.0, 12, tid=2, threads=1,
             pad=2048, native_us=800_000.0, gil_us=150_000.0), 0.8),
    cpu(span("serve.batch", 12.0, 0.5, 13, tid=6, lanes=2), 0.25),
    cpu(span("serve.batch", 13.0, 0.5, 14, tid=6, lanes=1), 0.125),
    cpu(span("front.conn", 11.5, 1.0, 15, tid=4, requests=1, bytes_in=150,
             bytes_out=80), 0.002),
    cpu(span("front.conn", 12.5, 1.0, 16, tid=5, requests=3, bytes_in=450,
             bytes_out=240), 0.004),
    cpu(span("front.conn", 9.0, 0.5, 17, tid=4, requests=1, bytes_in=150,
             bytes_out=80), 0.5),                            # ended before
] + [
    cpu(span("gil.probe", 10.0 + 0.5 * k, 0.4, 100 + k, tid=9,
             wait_us=100.0 * (k + 1)), 0.0001) for k in range(20)]


def read(params, ctx=None):
    return span_cpu.read(params, ctx or ctx_of(EVENTS))


def test_cpu_and_offcore_of_the_spans_that_end_in_the_window():
    # Three pages end inside (two downloaders'): 0.5 + 1.0 + 0.5 s of CPU.
    assert read({"span": "fetch.page", "take": "tdur"}) \
        == pytest.approx(2.0e6)
    assert read({"span": "fetch.page", "take": "tdur", "per": "entry"}) \
        == pytest.approx(2000.0)
    # get_entries: (1 - .25) + (3 - .5) + (.5 - .25) s off a core.
    assert read({"span": "fetch.get_entries", "take": "offcore",
                 "per": "entry"}) == pytest.approx(3500.0)
    assert read({"span": "serve.batch", "take": "tdur", "value": "mean",
                 "scale": 0.001}) == pytest.approx(187.5)
    with pytest.raises(ValueError):
        read({"span": "fetch.page", "take": "dur"})
    with pytest.raises(ValueError):
        read({"span": "fetch.page", "take": "tdur", "value": "median"})
    with pytest.raises(ValueError):
        read({"span": "fetch.page", "take": "tdur", "phase": "warmup"})


def test_terms_pool_and_an_argument_divides():
    # The gather's time off a core and the call's wait on its return:
    # (0.375 + 0.125) s and (0.05 + 0.15) s, over four batches.
    gil_wait = {"terms": [{"span": "decode.concat_b64", "take": "offcore"},
                          {"span": "decode.native_call",
                           "take": "arg:gil_us"}],
                "per": "batch", "scale": 0.001}
    assert read(gil_wait) == pytest.approx(175.0)
    # Two connections end inside, four requests between them.
    assert read({"span": "front.conn", "take": "tdur", "per": "arg:requests",
                 "scale": 0.001}) == pytest.approx(1.5)
    assert read({"span": "front.conn", "take": "arg:bytes_in"}) \
        == pytest.approx(600.0)


def test_the_probe_as_a_mean_and_as_a_percentile():
    # Twenty wake-ups, 100 to 2,000 us; the last ends at 19.9 s.
    assert span_mean.read({"span": "gil.probe", "arg": "wait_us",
                           "scale": 0.001}, ctx_of(EVENTS)) \
        == pytest.approx(1.05)
    p95 = {"span": "gil.probe", "take": "arg:wait_us", "value": "p95",
           "scale": 0.001}
    assert read(p95) == pytest.approx(1.9)  # the 19th of 20: nearest rank
    assert read(dict(p95, value="mean")) == pytest.approx(1.05)
    assert span_cpu.nearest_rank([5.0], 95) == 5.0
    assert span_cpu.nearest_rank([float(k) for k in range(1, 101)], 95) == 95.0


def test_absent_without_the_field_or_the_family_and_none_when_renamed():
    cpu_us = {"span": "fetch.page", "take": "tdur", "per": "entry"}
    bare = [{k: v for k, v in e.items() if k not in ("tts", "tdur")}
            for e in EVENTS]
    # A program older than the field: no span of its ring has a tdur.
    assert read(cpu_us, ctx_of(bare)) is ABSENT
    # ... and an argument needs no tdur: the probe reads as it did.
    assert read({"span": "gil.probe", "take": "arg:wait_us", "value": "p95"},
                ctx_of(bare)) == pytest.approx(1900.0)
    # Some spans have it and the named one does not: nothing to read.
    mixed = [bare[i] if e["name"] == "fetch.page" else e
             for i, e in enumerate(EVENTS)]
    assert read(cpu_us, ctx_of(mixed)) is None
    # No span of the family anywhere in the run: not in this program.
    no_front = [e for e in EVENTS if e["name"] != "front.conn"]
    assert read({"span": "front.conn", "take": "tdur",
                 "per": "arg:requests"}, ctx_of(no_front)) is ABSENT
    no_probe = [e for e in EVENTS if e["name"] != "gil.probe"]
    assert read({"span": "gil.probe", "take": "arg:wait_us"},
                ctx_of(no_probe)) is ABSENT
    # The family is there and the name is not, or it lost the argument.
    assert read({"span": "fetch.between_pages", "take": "tdur"}) is None
    stale = [dict(e, args={"threads": 1}) if e["name"] == "decode.native_call"
             else e for e in EVENTS]
    assert read({"span": "decode.native_call", "take": "arg:gil_us"},
                ctx_of(stale)) is None
    no_requests = [dict(e, args={}) if e["name"] == "front.conn" else e
                   for e in EVENTS]
    assert read({"span": "front.conn", "take": "tdur",
                 "per": "arg:requests"}, ctx_of(no_requests)) is None
    # A tracer that records no parents, or none at all.
    old = [{k: v for k, v in e.items() if k not in ("id", "parent")}
           for e in EVENTS]
    assert read(cpu_us, ctx_of(old)) is ABSENT


def test_nothing_to_read_from_a_window_the_ring_did_not_see_whole():
    cpu_us = {"span": "fetch.page", "take": "tdur"}
    later = [e for e in EVENTS if e["ts"] >= 12e6]
    assert read(cpu_us, ctx_of(later, dropped=7)) is None
    # Dropped, but the oldest event left ended before the window opened.
    assert read(cpu_us, ctx_of(EVENTS, dropped=7)) == pytest.approx(2.0e6)
    # Drops, and nobody can tell whether the family was ever there.
    no_front = [e for e in EVENTS if e["name"] != "front.conn"]
    assert read({"span": "front.conn", "take": "tdur"},
                ctx_of(no_front, dropped=7)) is None


def bench_json() -> dict:
    """As it stood before the cells listed after this file was written
    (``listing.py``)."""
    return listing.bench_json(ROOT)


def layer_file(name: str) -> dict:
    with open(os.path.join(BENCH, "layers", name + ".json")) as fh:
        return json.load(fh)


def test_the_seven_stand_at_the_end_of_the_list():
    listed = bench_json()["per_layer"]
    mine = listed[-7:]
    assert [m["name"] for m in mine] == list(GIL_METRICS)
    e2e = {m["name"] for m in bench_json()["end_to_end"]}
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["moves"] == "ingest_entries_per_s" and m["moves"] in e2e
        assert m["layer"] == "host threads (the GIL)"
        assert m["unit"] == ("us" if m["name"].endswith("us_per_entry")
                             else "ms")
        assert layer_file(m["name"])["reader"] in READERS
    assert [m["workloads"] for m in mine[:5]] == [ALL_CELLS] * 5
    assert [m["workloads"] for m in mine[5:]] == [["backfill-1log-query"]] * 2
    # No other metric reads the new reader, and no older entry moved.
    assert layer_file("gil.acquire_ms")["reader"] == "span_mean"
    assert [layer_file(n)["reader"] for n in GIL_METRICS[1:]] \
        == ["span_cpu"] * 6
    assert listed[-8]["name"] == "fold.meta_fallback_lanes"


def recorded():
    with gzip.open(os.path.join(HERE, "data", "recorded_threads_ring.json.gz"),
                   "rt") as fh:
        return json.load(fh)


def test_the_seven_on_the_recorded_ring():
    """The ring of a tiny CPU rehearsal of ``backfill-1log-query`` (the
    first eight 1,024-entry batches of its window, 64-entry pages),
    trimmed to the spans that end there. The numbers were read off it
    once and must not move; three are recounted here the slow way."""
    ctx = recorded()
    metrics, absent = layers.read_metrics(
        bench_json()["per_layer"][-7:], "backfill-1log-query", ctx)
    assert absent == []
    got = {k: v["value"] for k, v in metrics.items()}
    assert got == pytest.approx({
        "gil.acquire_ms": 0.40180363888888887,
        "gil.acquire_p95_ms": 1.594281,
        "decode.gil_wait_ms_per_batch": 0.20279637500000003,
        "fetch.cpu_us_per_entry": 10.520915649414063,
        "fetch.offcore_us_per_entry": 8.75501318359375,
        "serve.batch_cpu_ms": 4.903822166666667,
        "front.cpu_ms_per_request": 0.8833897142857143}, rel=1e-6)
    xs = [e for e in ctx["ring"]["events"] if e.get("ph") == "X"]
    assert all(0.0 <= e["tdur"] <= e["dur"] + 50.0 for e in xs)
    pages = [e for e in xs if e["name"] == "fetch.page"]
    assert got["fetch.cpu_us_per_entry"] == pytest.approx(
        sum(e["tdur"] for e in pages) / ctx["entries"])
    waits = sorted(e["args"]["wait_us"] for e in xs
                   if e["name"] == "gil.probe")
    assert got["gil.acquire_ms"] == pytest.approx(
        sum(waits) / len(waits) / 1e3)
    assert got["gil.acquire_p95_ms"] == pytest.approx(waits[34] / 1e3)  # of 36
    conns = [e for e in xs if e["name"] == "front.conn"]
    assert [e["args"]["requests"] for e in conns] == [1] * 7
    # The cells without queries list five, and read the same five.
    five, absent = layers.read_metrics(
        bench_json()["per_layer"][-7:], "backfill-3log", ctx)
    assert absent == [] and list(five) == list(GIL_METRICS[:5])
    assert {k: v["value"] for k, v in five.items()} \
        == {k: got[k] for k in GIL_METRICS[:5]}


def test_a_program_from_before_the_field_leaves_all_seven_out_by_name():
    """What the parent of the PR that brought them records: no ``tdur``,
    no ``gil.`` or ``front.`` family, the rest as it is."""
    ctx = recorded()
    events = [{k: v for k, v in e.items() if k not in ("tts", "tdur")}
              for e in ctx["ring"]["events"]
              if not e.get("name", "").startswith(("gil.", "front."))]
    for e in events:
        if "args" in e:
            e["args"] = {k: v for k, v in e["args"].items()
                         if k not in ("native_us", "gil_us")}
    old = dict(ctx, ring=dict(ctx["ring"], events=events))
    metrics, absent = layers.read_metrics(
        bench_json()["per_layer"][-7:], "backfill-1log-query", old)
    assert metrics == {} and absent == list(GIL_METRICS)
