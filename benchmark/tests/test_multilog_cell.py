"""Tests of the cell ``backfill-3log`` (configuration
``loglist3-dedup-1chip``): its committed files through a whole run at a
rehearsal's size, its control, and the readers its per-layer metrics
brought (``span_count``, ``counter_sum``, ``log_skew``):
python3 -m pytest benchmark/tests -q

The reference the counts are held to is ``fixture.py``'s arithmetic over
all three logs, which imports nothing of the program.
"""

from __future__ import annotations

import json
import os
import subprocess
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
from readers import counter_sum, log_skew, span_count  # noqa: E402
from test_span_ring import ctx_of, span  # noqa: E402

CELL = "backfill-3log"
# Read on the host: a rehearsal on the CPU has a number for each.
HOST_METRICS = (
    "multilog.full_saves", "multilog.drain_s", "multilog.cursor_wait_s",
    "multilog.partial_batches", "multilog.log_skew_pct",
    "multilog.sink_starved_share", "multilog.fetch_blocked_share",
    "multilog.fetch_http_us_per_entry", "multilog.decode_ns_per_entry",
    "multilog.fold_us_per_entry", "multilog.compile_programs",
    "multilog.loadgen_headroom_x")
# Read off the chip: nothing to read on the CPU.
DEVICE_METRICS = (
    "multilog.step_device_ns_per_entry", "multilog.sha256_roofline",
    "multilog.device_idle_pct", "multilog.peak_hbm_gb")
BATCHES = 60  # the window at --seconds 35; 72 with the ramp and the tail


def bench_json() -> dict:
    """As it stood before the cells listed after this file was written
    (``listing.py``)."""
    return listing.bench_json(ROOT)


def cell_metrics() -> list[dict]:
    return [m for m in bench_json()["per_layer"]
            if m.get("workloads") == [CELL]]


def layer_file(name: str) -> dict:
    with open(os.path.join(BENCH, "layers", name + ".json")) as fh:
        return json.load(fh)


def rehearse_cell(*args: str) -> list:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_cell.py"), CELL, *args],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert res.stdout.strip(), res.stderr[-2000:]
    return [json.loads(x) for x in res.stdout.strip().splitlines()]


def test_the_cell_lists_what_the_issue_names():
    bench = bench_json()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "loglist3-dedup-1chip", CELL, 1)
    listed = cell_metrics()
    assert sorted(m["name"] for m in listed) == sorted(
        HOST_METRICS + DEVICE_METRICS)
    assert {m["name"] for m in listed if m["moves"] == "setup_s"} == {
        "multilog.full_saves", "multilog.drain_s"}
    assert [m["name"] for m in bench["per_layer"][-16:]] \
        == [m["name"] for m in listed]  # at the end of the list
    with open(os.path.join(BENCH, "traffic", CELL + ".json")) as fh:
        (replay,) = json.load(fh)["generators"]
    with open(os.path.join(BENCH, "traffic", "backfill-1log.json")) as fh:
        alone = json.load(fh)["generators"][0]
    differ = ("logs", "window_entries_per_second", "ramp_batches",
              "tail_batches")
    assert {k: v for k, v in replay.items() if k not in differ} \
        == {k: v for k, v in alone.items() if k not in differ}
    assert [replay[k] for k in differ] == [3, 112000, 6, 6]
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == "loglist3-dedup-1chip")
    with open(os.path.join(ROOT, config_entry["file"])) as fh:
        config = json.load(fh)
    with open(os.path.join(BENCH, "configs", "icarus-dedup-1chip.json")) as fh:
        dedup = json.load(fh)
    assert config["directives"] == dedup["directives"]
    assert set(dedup["guarantees"]) < set(config["guarantees"])
    assert sorted(config["reduced"]) == sorted(config_entry["reduced"]) \
        == sorted(["table_prefill", "issuers", "tableBits", "chips",
                   "cross_log_duplicates"])
    assert config["source"] == config_entry["source"]


def test_the_committed_cell_is_correct_and_every_host_metric_reads():
    """The committed files at the tiny cut, traced: ``correct``, no
    entry failed, nothing folded beyond the fixture's batches; the
    round after the warm-up round holds ONE full checkpoint for its
    three logs and no dispatch short of a batch; every metric read on
    the host has a number and the four read off the chip are left out,
    not failed."""
    lines = rehearse_cell("31352", "trace")
    line = lines[-1]
    assert line["correct"] is True, line["not_ok"]
    assert line["by_generator"] == {"log_replay": {
        "attempted": BATCHES * 1024, "failed": 0}}
    metrics = next(x for x in lines if isinstance(x, list))[0]
    assert sorted(metrics) == sorted(HOST_METRICS)
    values = {k: v["value"] for k, v in metrics.items()}
    assert all(isinstance(v, float) for v in values.values())
    assert values["multilog.full_saves"] == 1.0
    assert values["multilog.partial_batches"] == 0.0
    assert 0.0 <= values["multilog.log_skew_pct"] < 50.0
    assert 0.0 < values["multilog.cursor_wait_s"] \
        <= values["multilog.drain_s"] + 1.0
    for name in set(HOST_METRICS) - {"multilog.partial_batches",
                                     "multilog.log_skew_pct"}:
        assert values[name] > 0.0, name
    # Three downloaders' waits add up: the share is of 300.
    assert values["multilog.fetch_blocked_share"] <= 300.0
    assert not any("absent" in x for x in lines if isinstance(x, dict))


def test_lost_entry_in_the_committed_cell_is_not_correct():
    line = rehearse_cell("31353", "lost_entry")[-1]
    assert line["correct"] is False
    assert "durable report: unique serials" in line["not_ok"]
    assert line["by_generator"]["log_replay"]["failed"] > 0


# A round from 8 (the log opens) to 30 (durable) on the run's clock, the
# window 10 to 20: three downloaders' waits, the round's one save, the
# warm-up round's save before the log opened.
ROUND = [
    span("ckpt.save", 5.0, 2.0, 1, kind="full", reason="exit"),  # warm-up
    span("round.cursor_wait", 21.0, 8.5, 2, tid=1, log="a", how="covered"),
    span("round.cursor_wait", 21.5, 8.0, 3, tid=2, log="b", how="covered"),
    span("round.cursor_wait", 22.0, 7.5, 4, tid=3, log="c", how="closed"),
    span("round.save", 22.0, 7.5, 5, parent=4, tid=3, logs=3, cursors=3),
    span("ckpt.save", 22.5, 6.0, 6, parent=5, tid=3, kind="full",
         reason="exit"),
    span("ckpt.save", 29.8, 0.1, 7, tid=3, kind="noop"),
]


def read_one(name: str, ctx: dict):
    spec = layer_file(name)
    assert spec["reader"] in ("span_count", "counter_sum", "log_skew")
    return {"span_count": span_count, "counter_sum": counter_sum,
            "log_skew": log_skew}[spec["reader"]].read(
        spec.get("params", {}), ctx)


def test_span_count_on_spans_written_by_hand():
    ctx = ctx_of(ROUND, t_open=8.0)
    assert read_one("multilog.full_saves", ctx) == 1.0
    assert read_one("multilog.cursor_wait_s", ctx) == pytest.approx(8.0)
    # What the parent of the PR that brought the cell did: a full save a
    # log, and no span of the family ``round.``.
    parent = [e for e in ROUND if not e["name"].startswith("round.")] + [
        span("ckpt.save", 21.0, 0.2, 8, kind="full", reason="exit"),
        span("ckpt.save", 21.4, 0.2, 9, kind="full", reason="exit")]
    ctx = ctx_of(parent, t_open=8.0)
    assert read_one("multilog.full_saves", ctx) == 3.0
    assert read_one("multilog.cursor_wait_s", ctx) is ABSENT
    # The family is there and no span of the name ended in the round: a
    # count reads 0, a mean has nothing to read.
    late = ctx_of(ROUND, t_open=8.0, t_durable=20.5)
    assert read_one("multilog.full_saves", late) == 0.0
    assert read_one("multilog.cursor_wait_s", late) is None
    # A ring that forgot events and holds none from before the round.
    assert read_one("multilog.full_saves",
                    ctx_of(ROUND[1:], dropped=3, t_open=8.0)) is None
    # A tracer that records no parents is an older program's.
    bare = [{k: v for k, v in e.items() if k != "parent"} for e in ROUND]
    assert read_one("multilog.full_saves", ctx_of(bare, t_open=8.0)) is ABSENT
    assert span_count.phase_bounds("window", ctx["out"]) == (10.0, 20.0)
    with pytest.raises(ValueError):
        span_count.phase_bounds("nowhere", ctx["out"])


def test_counter_sum_tells_none_short_from_not_counted():
    out = {"t_open": 8.0, "t_durable": 30.0, "t_first": 10.0,
           "t_folded": 20.0}
    whole = [(t, "ingest.partial_batches", 0.0) for t in (9.0, 12.0, 25.0)]
    ctx = {"out": dict(out, counters=whole + [(12.0, "other", 5.0)])}
    assert read_one("multilog.partial_batches", ctx) == 0.0
    short = whole + [(26.0, "ingest.partial_batches", 1.0),
                     (31.0, "ingest.partial_batches", 1.0)]  # next round's
    assert read_one("multilog.partial_batches",
                    {"out": dict(out, counters=short)}) == 1.0
    # A program that never says the counter does not count it.
    assert read_one("multilog.partial_batches", {"out": dict(
        out, counters=[(12.0, "other", 5.0)])}) is ABSENT
    assert counter_sum.read(
        {"key": "ingest.partial_batches", "phase": "window"},
        {"out": dict(out, counters=short)}) == 0.0
    with pytest.raises(ValueError):
        counter_sum.read({"key": "k", "phase": "nowhere"},
                         {"out": dict(out, counters=[(1.0, "k", 1.0)])})


def test_log_skew_from_page_stamps_written_by_hand():
    """Three logs of 100 entries a round (log 0 after a warm-up prefix
    of 50), pages of 10: at ``t_folded`` the server had written 80, 60
    and 70 of them."""
    def pages(log, first, n_pages, t0, step):
        return [(log, first + 10 * k, 10, t0 + k * step - 0.01,
                 t0 + k * step, t0 + k * step + 0.01) for k in range(n_pages)]

    stamps = (pages(0, 50, 10, 10.0, 1.25) + pages(1, 0, 10, 10.0, 1.8)
              + pages(2, 0, 10, 10.0, 1.5))
    out = {"t_folded": 19.0, "pages": sorted(stamps, key=lambda p: p[4])}
    assert log_skew.read({}, {"out": out}) == pytest.approx(20.0)
    # One log: no skew. No page at all: nothing to read.
    alone = {"t_folded": 19.0, "pages": pages(0, 50, 10, 10.0, 1.25)}
    assert log_skew.read({}, {"out": alone}) == 0.0
    assert log_skew.read({}, {"out": {"t_folded": 19.0, "pages": []}}) is None


def test_the_readers_take_their_place_in_a_traced_line():
    """``layers.read_metrics`` over the cell's ring metrics: the parent
    leaves out by name what only this program has, and reads the rest."""
    mine = [m for m in cell_metrics() if m["name"] in (
        "multilog.full_saves", "multilog.cursor_wait_s",
        "multilog.partial_batches")]
    ctx = ctx_of(ROUND, t_open=8.0, counters=[
        (9.0, "ingest.partial_batches", 0.0)])
    metrics, absent = layers.read_metrics(mine, CELL, ctx, strict=True)
    assert absent == [] and {k: v["value"] for k, v in metrics.items()} == {
        "multilog.full_saves": 1.0, "multilog.cursor_wait_s": 8.0,
        "multilog.partial_batches": 0.0}
    old = ctx_of([e for e in ROUND if not e["name"].startswith("round.")],
                 t_open=8.0, counters=[])
    metrics, absent = layers.read_metrics(mine, CELL, old, strict=True)
    assert sorted(absent) == ["multilog.cursor_wait_s",
                              "multilog.partial_batches"]
    assert list(metrics) == ["multilog.full_saves"]
