"""Tests of the cell ``backfill-1log-growing`` and of what it added to
the benchmark: the configuration and traffic files as ISSUE 48 names
them, every listed metric's reader, ``rehash_cost.py``'s bytes, the two
readers, the generator ``table_watch`` on recorded rows and as a
process, a rehearsal of the committed cell and its control. By hand, on
the CPU:

  python3 -m pytest benchmark/tests/test_growing_cell.py -q

Tier-1 runs them through ``tests/test_benchmark_growing_cell.py``. They
find the cell's entries by NAME, never by place, so that a cell listed
after this one breaks none of them.
"""

from __future__ import annotations

import http.server
import json
import os
import subprocess
import sys
import threading
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import harness  # noqa: E402
import layers  # noqa: E402
import prefill  # noqa: E402
import rehash_cost  # noqa: E402
from generators import table_watch  # noqa: E402
from layers import ABSENT  # noqa: E402
from readers import rehash_roofline, span_before_open  # noqa: E402
from test_span_ring import ctx_of, span  # noqa: E402

CELL = "backfill-1log-growing"
CONFIG = "icarus-dedup-growing-1chip"
LOADED = "backfill-1log-loaded"
# The cell's metrics (each lists it alone) and, where a metric is read
# by an older metric's file under this cell's name, that file's. The
# thirteen fill ``per_layer`` to the 128 it may hold; the run's restore,
# saved rows, programs compiled and starved sink stay in the result
# line's ``setup``, checks and ``compile`` block, unlisted.
OWN = {
    "growing.grow_s": None, "growing.rehash_s": None,
    "growing.rehash_device_s": None, "growing.rehash_roofline": None,
    "growing.prepare_s": None, "growing.grows": None,
    "growing.host_bytes": None, "growing.unprepared": None,
    "growing.rehomed_rows": None,
    "growing.table_load_pct": "loaded.table_load_pct",
    "growing.step_device_ns_per_entry": "step.device_ns_per_entry",
    "growing.peak_hbm_gb": "device.peak_hbm_gb",
    "growing.device_idle_pct": "device.idle_pct",
}
GROWS = ("table_watch: growth: grow-and-rehash events between the log's "
         "opening and the last poll")
SLOTS = "table_watch: growth: slots of the live table at the last poll"
ROWS = "table_watch: growth: rows in the live table at the last poll"


def bench_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def named(entries: list, name: str) -> dict:
    return next(e for e in entries if e["name"] == name)


def json_file(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


# -- what BENCHMARK.json lists ---------------------------------------------


def test_the_cell_and_its_configuration_are_what_the_issue_names():
    bench = bench_json()
    cell = named(bench["workloads"], CELL)
    assert cell["config"] == CONFIG and cell["traffic"] == CELL
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) \
        <= len(bench["workloads"]) // 2
    entry = named(bench["configs"], CONFIG)
    assert entry["reduced"] == ["issuers", "tableBits"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    config = json_file("configs", CONFIG + ".json")
    plain = json_file("configs", "icarus-dedup-1chip.json")
    loaded = json_file("configs", "icarus-dedup-loaded-1chip.json")
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    # Nothing is set to make the growth happen: the ten directives of
    # the plain dedup configuration, to the letter.
    assert config["directives"] == plain["directives"]
    assert "tableGrowAt" not in config["directives"]
    assert config["guarantees"] == dict(
        plain["guarantees"], resume=loaded["guarantees"]["resume"],
        growth=config["guarantees"]["growth"])
    assert "exactly once" in config["guarantees"]["growth"]
    assert set(config["reduced"]) == {"issuers", "tableBits"}
    assert set(config["assumed"]) == {"load", "known_share",
                                      *plain["assumed"]}
    traffic = json_file("traffic", CELL + ".json")
    theirs = json_file("traffic", LOADED + ".json")["generators"]
    assert traffic["generators"] == [
        dict(theirs[0], table_prefill={"slots_log2": 26, "load": 0.691,
                                       "known_share": 0.5}),
        {"kind": "table_watch", "poll_s": 0.5, "answer_within_s": 1.0}]
    spec = harness.log_spec(traffic, float(bench["run_seconds"]), 65536)
    assert spec.window_entries == 40 * 65536 and spec.per_log == 47 * 65536
    slots = prefill.table_slots(26)
    assert slots == 100_663_296 and spec.standing.rows == 69_558_338
    # The growth falls inside the window: the threshold is ~906K rows
    # above the standing table, a batch brings ~31.8K new ones, and the
    # policy waits for the exact count + the batch coming to pass it.
    gap = 0.7 * slots - spec.standing.rows
    per_batch = 65536 * (1 - 0.03) * 0.5
    batch = (gap - 65536) / per_batch + 1  # the batch whose submit grows
    assert 1 + 4 + 10 <= batch <= 1 + 4 + 30  # warm-up, ramp, window 10-30
    # The mark of `prepare_growth` lies under the cell's load and over
    # the loaded cell's, and the end-to-end metrics are as they were.
    assert 0.5076 < 15 / 16 * 0.7 < 0.691
    assert [(m["name"], m["bound"]) for m in bench["end_to_end"]] == [
        ("ingest_entries_per_s", 0.16), ("setup_s", 0.25)]


def test_every_listed_metric_has_its_reader():
    bench = bench_json()
    mine = [m for m in bench["per_layer"] if CELL in m.get("workloads", ())]
    assert {m["name"] for m in mine} == set(OWN)
    assert len(bench["per_layer"]) <= 128  # the file's own limit
    assert all(m["workloads"] == [CELL] for m in mine)
    older_layers = {m["layer"] for m in bench["per_layer"] if m not in mine}
    for m in mine:
        assert m["layer"] in older_layers  # no layer of its own spelling
        spec = json_file("layers", m["name"] + ".json")
        assert os.path.exists(os.path.join(
            BENCH, "readers", spec["reader"] + ".py"))
        older = OWN[m["name"]]
        if older is not None:
            with open(os.path.join(BENCH, "layers", m["name"] + ".json"),
                      "rb") as a, open(os.path.join(
                          BENCH, "layers", older + ".json"), "rb") as b:
                assert a.read() == b.read()
            was = named(bench["per_layer"], older)
            assert {k: v for k, v in m.items()
                    if k not in ("name", "workloads")} \
                == {k: v for k, v in was.items()
                    if k not in ("name", "workloads")}
    roofline = named(mine, "growing.rehash_roofline")
    assert roofline["unit"] == "%" and roofline["source"] == "device_trace"
    assert named(mine, "growing.prepare_s")["moves"] == "setup_s"
    assert json_file("layers", "growing.grows.json") == {
        "reader": "counter_sum",
        "params": {"key": "aggregator.table_grow", "phase": "round"}}
    # No other cell reads them.
    ctx = {"out": {"counters": [], "t_open": 0.0, "t_durable": 1.0}}
    assert layers.read_metrics(mine, "backfill-1log", ctx) == ({}, [])


# -- the bytes and the readers ----------------------------------------------


def ring_ctx(events: list, **more) -> dict:
    ctx = ctx_of(events)
    ctx["out"].update(t_main_called=1.0, t_open=9.0)
    ctx.update(more)
    return ctx


def test_a_growth_moves_the_old_table_once_and_the_new_once():
    assert rehash_cost.table_double(26) == {
        "hbm_bytes": ((1 << 26) + (1 << 27)) * 32}
    assert rehash_cost.table_double(26)["hbm_bytes"] == 6_442_450_944
    grown = span("grow.rehash", 15.0, 0.5, ident=2, parent=1)
    trace = {"modules": {"jit_grow_rehash(77)": 0.25, "jit_ingest_core(5)": 2.0},
             "module_calls": {"jit_grow_rehash(77)": 1, "jit_ingest_core(5)": 40}}
    ctx = ring_ctx([span("grow.table", 14.9, 0.7, ident=1), grown],
                   trace=trace, device={"kind": "TPU v5 lite"},
                   config={"directives": {"tableBits": 26}})
    params = {"match": ["jit_grow_rehash"]}
    assert rehash_roofline.read(params, ctx) == pytest.approx(
        100.0 * 6_442_450_944 / 819e9 / 0.25)
    assert rehash_roofline.read(dict(params, what="seconds"), ctx) == 0.25
    # Two growths in the window: two calls' bytes over both calls' time.
    trace["module_calls"]["jit_grow_rehash(77)"] = 2
    assert rehash_roofline.read(params, ctx) == pytest.approx(
        200.0 * 6_442_450_944 / 819e9 / 0.25)
    # The family is there and the window's profile has no such module:
    # the growth fell outside the window, or the program was renamed.
    ctx["trace"] = {"modules": {"jit_ingest_core(5)": 2.0},
                    "module_calls": {"jit_ingest_core(5)": 40}}
    assert rehash_roofline.read(params, ctx) is None
    # A program that grows through the host has no span of the family.
    old = ring_ctx([span("device.fold", 15.0, 0.1, ident=1)], trace=trace,
                   device=ctx["device"], config=ctx["config"])
    assert rehash_roofline.read(params, old) is ABSENT
    assert rehash_roofline.read(params, dict(old, ring=None)) is ABSENT


def test_a_span_of_the_set_up_is_read_between_main_and_the_opening():
    """``ctx_of``'s ring has ``mono_t0`` 0 and its window at (10, 20]:
    the set-up here lies in (1, 9]."""
    prepared = span("grow.prepare", 4.0, 3.5, ident=1, from_slots=384,
                    to_slots=768, programs=4)
    later = span("grow.prepare", 25.0, 3.0, ident=2, from_slots=768,
                 to_slots=1536, programs=4)
    ctx = ring_ctx([prepared, later, span("grow.table", 15.0, 0.5, ident=3)])
    params = {"span": "grow.prepare"}
    assert span_before_open.read(params, ctx) == pytest.approx(3.5)
    assert span_before_open.read(dict(params, arg="programs"), ctx) == 4.0
    assert span_before_open.read(dict(params, arg="nope"), ctx) is None
    assert span_before_open.read({"span": "grow.table"}, ctx) is None
    old = ring_ctx([span("device.fold", 5.0, 0.1, ident=1)])
    assert span_before_open.read(params, old) is ABSENT
    assert span_before_open.read(params, dict(old, ring=None)) is ABSENT


# -- the generator -----------------------------------------------------------


class Run:
    """What ``summarise`` asks of the fixture."""

    def __init__(self, unique: int):
        self.unique = unique

    def expected_unique(self) -> int:
        return self.unique


CONFIG_26 = {"directives": {"tableBits": 26}}
OLD, NEW = 100_663_296, 201_326_592


def polls(*states, t0=50.0):
    """``(grow, slots, rows)`` a poll, half a second apart from the
    log's opening at 50; None for a poll without an answer."""
    out = []
    for k, state in enumerate(states):
        asked = t0 + 0.5 * k
        if state is None:
            out.append([asked, None, None, None, None])
        else:
            grow, slots, rows = state
            out.append([asked, asked + 0.004, float(grow),
                        None if slots is None else float(slots),
                        None if slots is None else rows / slots])
    return {"polls": out}


def watched(handed, unique=71_080_000):
    window = {"t_open": 50.0, "t_first": 51.0, "t_folded": 60.0}
    got = table_watch.summarise(handed, window, Run(unique), CONFIG_26)
    assert sorted(got["values"]) == sorted(table_watch.VALUES)
    assert got["failed"] == 0
    return got, [c["got"] == c["want"] for c in got["checks"]]


def test_the_watcher_holds_the_table_to_one_growth_and_the_exact_rows():
    before = (0, OLD, 69_600_000)
    sound = polls(before, before, None, (1, NEW, 70_500_000),
                  (1, NEW, 71_080_000))
    got, ok = watched(sound)
    assert ok == [True, True, True] and got["attempted"] == 5
    assert [c["what"] for c in got["checks"]] == [
        what.split(": ", 1)[1] for what in (GROWS, SLOTS, ROWS)]
    assert [c["want"] for c in got["checks"]] == [1, NEW, 71_080_000]
    assert got["values"]["watch.unanswered_polls"] == 1.0
    assert got["values"]["watch.grow_seen_s"] == pytest.approx(1.504)
    # A poll from before the log opened is not the run's.
    early = polls((0, OLD, 1), t0=40.0)["polls"] + sound["polls"]
    assert watched({"polls": early})[0]["attempted"] == 5
    # No growth: the first check says so, and the slots are the old ones.
    got, ok = watched(polls(before, (0, OLD, 71_080_000)))
    assert ok == [False, False, True]
    assert got["values"]["watch.grow_seen_s"] is None
    # Two growths.
    got, ok = watched(polls(before, (1, NEW, 70_000_000),
                            (2, 2 * NEW, 71_080_000)))
    assert ok == [False, False, True] and got["checks"][0]["got"] == 2
    # A row short.
    _, ok = watched(polls(before, (1, NEW, 71_079_999)))
    assert ok == [True, True, False]
    # A table that had grown before the log opened grew at the wrong time.
    _, ok = watched(polls((1, NEW, 69_600_000), (1, NEW, 71_080_000)))
    assert ok == [False, True, True]
    # A program without the gauge of its slots is held to it.
    got, ok = watched(polls((0, None, 0), (1, None, 0)))
    assert ok == [True, False, False]
    assert got["checks"][1]["got"] is None and got["checks"][2]["got"] is None
    with pytest.raises(ValueError, match="no poll"):
        watched(polls(None, None))


def test_the_exposition_is_read_by_name():
    text = ("# TYPE aggregator_table_grow counter\naggregator_table_grow 1\n"
            "# TYPE aggregator_table_load gauge\n"
            "aggregator_table_load 0.35306271910667419\n"
            "aggregator_table_slots 201326592\n"
            "aggregator_table_load_other 9\n")
    assert table_watch.parse(text) == [1.0, 201326592.0,
                                       0.35306271910667419]
    assert round(0.35306271910667419 * 201326592) == 71_080_914
    # A counter never added to reads 0; a gauge never set, nothing.
    assert table_watch.parse("ct_fetch_foldedEntries 3\n") == [0.0, None,
                                                               None]


class Exposition(http.server.BaseHTTPRequestHandler):
    state = {"text": "aggregator_table_slots 384\naggregator_table_load 0.5\n",
             "sleep": 0.0}

    def do_GET(self):  # noqa: N802
        time.sleep(self.state["sleep"])
        body = self.state["text"].encode()
        self.send_response(200 if self.path == "/metrics" else 404)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *_args):
        pass


def test_the_watcher_as_a_process_polls_from_the_opening_to_the_stop(
        tmp_path):
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Exposition)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    spec = {"generator": {"kind": "table_watch", "poll_s": 0.05,
                          "answer_within_s": 0.2},
            "seed": 1, "seconds": 1.0, "log_spec": {}, "log_port": 0,
            "ports": {"metricsPort": server.server_address[1]}, "cores": [],
            "rows": str(tmp_path / "table_watch.rows.json")}
    (tmp_path / "spec.json").write_text(json.dumps(spec))
    child = subprocess.Popen(
        [sys.executable, os.path.join(BENCH, "generators", "table_watch.py"),
         str(tmp_path / "spec.json")], stdin=subprocess.PIPE,
        stdout=subprocess.PIPE, text=True)

    def tell(**message):
        child.stdin.write(json.dumps(message) + "\n")
        child.stdin.flush()

    try:
        tell(warm=time.monotonic())
        assert json.loads(child.stdout.readline()) == {"ready": True}
        time.sleep(0.2)
        opened = time.monotonic()
        tell(opened=opened)
        time.sleep(0.4)
        Exposition.state["sleep"] = 0.5  # the program is busy: no answer
        time.sleep(0.6)
        Exposition.state.update(sleep=0.0, text=(
            "aggregator_table_grow 1\naggregator_table_slots 768\n"
            "aggregator_table_load 0.375\n"))
        tell(folded=time.monotonic())
        time.sleep(0.3)  # it goes on past the fold
        tell(stop=True)
        said = json.loads(child.stdout.readline())
        with open(said["rows"]) as fh:
            handed = json.load(fh)
    finally:
        child.stdin.close()
        assert child.wait(timeout=30) == 0
        server.shutdown()
        Exposition.state.update(
            sleep=0.0,
            text="aggregator_table_slots 384\naggregator_table_load 0.5\n")
    rows = handed["polls"]
    assert said["polls"] == len(rows) >= 8
    assert all(r[0] >= opened for r in rows)  # none before the opening
    assert rows[0][2:] == [0.0, 384.0, 0.5]
    assert rows[-1][2:] == [1.0, 768.0, 0.375]  # the last poll, at the stop
    assert any(r[1] is None for r in rows)
    got = table_watch.summarise(
        handed, {"t_open": opened, "t_first": opened, "t_folded": opened},
        Run(288), {"directives": {"tableBits": 8}})  # 16 buckets, 384 slots
    assert [c["got"] == c["want"] for c in got["checks"]] == [True] * 3
    assert got["values"]["watch.unanswered_polls"] >= 1.0


# -- the committed cell, cut to a rehearsal's size ---------------------------


@pytest.fixture
def checkout(tmp_path):
    """A directory shaped like the checkout (links to ``benchmark/``, the
    package and ``BENCHMARK.json``) for a rehearsal to keep its state in;
    ``benchmark/`` computes every path from where its files lie."""
    root = tmp_path / "growing_cell_checkout"  # tier-1's own is "checkout"
    root.mkdir()
    for name in ("benchmark", "ct_mapreduce_tpu", "BENCHMARK.json"):
        os.symlink(os.path.join(ROOT, name), root / name)
    return str(root)


def rehearse_cell(root: str, *args: str) -> list:
    """``breaks_growing.py rehearse``: ``rehearse_cell.py``'s run (the
    cell's files at ``tableBits`` 18, batches of 1,024) with this
    cell's control known beside the older ones."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    res = subprocess.run(
        [sys.executable,
         os.path.join(root, "benchmark", "tests", "breaks_growing.py"),
         "rehearse", CELL, *args], capture_output=True, text=True,
        timeout=600, env=env, cwd=root)
    assert res.stdout.strip(), res.stderr[-2000:]
    return [json.loads(x) for x in res.stdout.strip().splitlines()], res.stderr


def checks_of(lines: list) -> dict:
    return {x["what"]: x for x in lines if isinstance(x, dict) and "what" in x}


def test_the_committed_cell_grows_once_and_is_correct(checkout):
    """Traced: the thirteen comparisons, no program compiled in the
    round, no table byte through the host, the programs ready."""
    lines, stderr = rehearse_cell(checkout, "778001", "trace")
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0, lines[-16:]
    checks = checks_of(lines)
    assert len(checks) == 13 and list(checks)[-3:] == [GROWS, SLOTS, ROWS]
    slots = prefill.table_slots(18)
    assert checks[SLOTS]["want"] == 2 * slots == 786_432
    assert checks[ROWS]["want"] \
        == checks["durable report: unique serials"]["want"] > 0.691 * slots
    assert checks["round: programs compiled"]["got"] == 0
    assert f"table grown {slots} → {2 * slots} slots" in stderr
    assert "0 B through the host" in stderr
    assert last["by_generator"]["table_watch"]["attempted"] > 0
    assert 0.0 < last["values"]["watch.grow_seen_s"] < 60.0
    metrics = next(x for x in lines if isinstance(x, list))[0]
    assert metrics["growing.grows"]["value"] == 1.0
    assert metrics["growing.host_bytes"]["value"] == 0.0
    assert metrics["growing.unprepared"]["value"] == 0.0
    assert metrics["growing.rehomed_rows"]["value"] > 0.0
    assert metrics["growing.prepare_s"]["value"] > 0.0
    # The load the program states at the end is the rows the report
    # counts over the doubled table's slots.
    assert metrics["growing.table_load_pct"]["value"] == pytest.approx(
        100.0 * checks[ROWS]["got"] / (2 * slots))
    assert set(metrics) <= set(OWN)  # the listed thirteen, no other cell's


def test_growth_disabled_is_not_correct(checkout):
    """The control: ``tableGrowAt = 0`` in the ini. Counts stay exact and
    every comparison of the log holds; the watcher's first check reads
    no growth, its second the old slots."""
    lines, stderr = rehearse_cell(checkout, "778002", "growth_disabled")
    checks = checks_of(lines)
    assert lines[-1]["correct"] is False and lines[-1]["failed"] == 0
    assert lines[-1]["not_ok"] == [GROWS, SLOTS]
    assert checks[GROWS]["got"] == 0
    assert checks[SLOTS]["got"] == prefill.table_slots(18)
    assert checks[ROWS]["ok"] is True
    assert "table grown" not in stderr
