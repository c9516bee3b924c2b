"""Tests of the cell ``backfill-3log-query-shard4`` (configuration
``loglist3-serve-shard4``): its committed files against its two parents'
(``loglist3-shard4`` and ``icarus-serve-1chip``; ``backfill-3log.json``
and ``backfill-1log-query.json``), a whole run of them at a rehearsal's
size on four of the CPU's virtual devices, its control, and the two
readers its per-layer metrics brought (``shard_copy_roofline``,
``counter_ratio``):
python3 -m pytest benchmark/tests -q

The reference the answers are held to is ``fixture.py``'s arithmetic and
the log server's page stamps (``generators/query_poisson.py::summarise``),
which import nothing of the program.
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
import tracing  # noqa: E402
from layers import ABSENT  # noqa: E402
from readers import copy_roofline, counter_ratio, shard_copy_roofline  # noqa: E402

CELL = "backfill-3log-query-shard4"
CONFIG = "loglist3-serve-shard4"
CHIPS = 4
# Read on the host: a rehearsal on the CPU has a number for each.
HOST_METRICS = (
    "qshard4.client_p95_ms", "qshard4.client_p99_ms", "qshard4.query_failed",
    "qshard4.probe_ms", "qshard4.device_calls_per_batch",
    "qshard4.shards_per_batch", "qshard4.padded_lane_share",
    "qshard4.batch_cpu_ms", "qshard4.batcher_busy_share",
    "qshard4.snapshot_ms", "qshard4.snapshot_locked_ms",
    "qshard4.fetch_offcore_us_per_entry", "qshard4.sink_starved_share",
    "qshard4.compile_programs")
# Read off the chips: nothing to read on the CPU.
DEVICE_METRICS = (
    "qshard4.step_device_ns_per_entry", "qshard4.device_idle_pct",
    "qshard4.peak_hbm_gb", "qshard4.snapshot_copy_roofline")
# What hangs on the program's ``qshard.`` family: a program from before
# it leaves these out of a traced line, by name, and reads the rest.
QSHARD_FAMILY = (
    "qshard4.probe_ms", "qshard4.device_calls_per_batch",
    "qshard4.shards_per_batch", "qshard4.padded_lane_share")
# The parents' readers under this cell's names, to the letter.
SAME_FILE = {
    "client_p95_ms": "serve.client_p95_ms",
    "client_p99_ms": "serve.client_p99_ms",
    "query_failed": "serve.query_failed",
    "batch_cpu_ms": "serve.batch_cpu_ms",
    "batcher_busy_share": "serve.batcher_busy_share",
    "snapshot_ms": "serve.snapshot_ms",
    "snapshot_locked_ms": "snapshot.locked_ms",
    "fetch_offcore_us_per_entry": "fetch.offcore_us_per_entry",
    "sink_starved_share": "shard4.sink_starved_share",
    "step_device_ns_per_entry": "shard4.step_device_ns_per_entry",
    "device_idle_pct": "shard4.device_idle_pct",
    "peak_hbm_gb": "shard4.peak_hbm_gb",
    "compile_programs": "shard4.compile_programs"}
BATCHES = 60  # the window at --seconds 35, as backfill-3log's


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


def config_of(bench: dict, name: str) -> tuple[dict, dict]:
    entry = next(c for c in bench["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, entry["file"])) as fh:
        return entry, json.load(fh)


def traffic_of(name: str) -> dict:
    with open(os.path.join(BENCH, "traffic", name + ".json")) as fh:
        return json.load(fh)


def rehearse_cell(*args: str) -> list:
    """The committed cell at the tiny cut, its mesh over four virtual
    devices of the CPU."""
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=" ".join(
        flags + [f"--xla_force_host_platform_device_count={CHIPS}"]))
    res = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_cell.py"), CELL, *args],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert res.stdout.strip(), res.stderr[-2000:]
    return [json.loads(x) for x in res.stdout.strip().splitlines()]


def test_the_cell_is_its_two_parents_and_nothing_else():
    bench = bench_json()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "backfill-3log-query", CHIPS)
    # Half of the cells, rounded down, may ask for four chips.
    four = sum(w["chips"] == CHIPS for w in bench["workloads"])
    assert four == 2 <= len(bench["workloads"]) // 2
    entry, config = config_of(bench, CONFIG)
    _e, shard = config_of(bench, "loglist3-shard4")
    _e, serve = config_of(bench, "icarus-serve-1chip")
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    assert entry["source"] not in (shard["source"], serve["source"])
    assert (config["chips"], config["ports"]) == (CHIPS, ["queryPort"])
    # The sharded file's eleven directives with tableBits 28 (2^26 slots
    # a chip, the one-chip control's share), and the serve file's two.
    mine = dict(config["directives"])
    assert (mine.pop("serveReplicas"), mine.pop("serveDevice")) == (
        serve["directives"]["serveReplicas"],
        serve["directives"]["serveDevice"]) == (2, True)
    assert mine.pop("tableBits") == 28 == shard["directives"]["tableBits"] + 2
    assert mine == {k: v for k, v in shard["directives"].items()
                    if k != "tableBits"}
    assert mine["meshShape"] == f"shard:{CHIPS}"
    assert int(config["directives"]["tableBits"]) - 2 \
        == serve["directives"]["tableBits"]  # a chip's share is the control's
    # Guarantees: the sharded file's eight word for word, the serve
    # file's three with this traffic's age, and routing.
    added = dict(config["guarantees"])
    for key in shard["guarantees"]:
        assert added.pop(key) == shard["guarantees"][key], key
    assert len(shard["guarantees"]) == 8
    assert added.pop("staleness") == serve["guarantees"]["staleness"]
    assert added.pop("deadline") == serve["guarantees"]["deadline"]
    assert added.pop("membership") == serve["guarantees"]["membership"] \
        .replace("at least 30 s", "at least 10 s")
    assert "hashes to" in added.pop("routing") and not added
    # tableBits is not cut here; the other cuts are the parents'.
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == sorted(
        ["table_prefill", "issuers", "cross_log_duplicates", "chips"])
    assert all(config["reduced"][k] == shard["reduced"][k]
               for k in config["reduced"])
    assert "55%" in config["not_reduced"]["tableBits"]
    assert {k: config["assumed"][k] for k in shard["assumed"]} \
        == shard["assumed"]
    assert config["assumed"]["min_age_s"] == 10
    assert config["assumed"]["why_min_age_10"]


def test_the_traffic_is_the_controls_log_and_the_one_chip_cells_readers():
    mine = traffic_of("backfill-3log-query")
    replay, queries = mine["generators"]
    assert replay == traffic_of("backfill-3log")["generators"][0]
    theirs = traffic_of("backfill-1log-query")["generators"][1]
    assert queries == dict(theirs, min_age_s=10)
    assert (queries["rate_per_s"], queries["deadline_s"],
            queries["known_share"], queries["zipf_s"]) == (96, 10, 0.95, 0.99)
    assert queries["warmup_lanes"] == [16 << k for k in range(9)]
    _entry, config = config_of(bench_json(), CONFIG)
    assert config["assumed"]["min_age_s"] == queries["min_age_s"]
    assert f"at least {queries['min_age_s']} s" \
        in config["guarantees"]["membership"]


def test_every_metric_of_the_cell_names_a_reader_that_exists():
    bench = bench_json()
    listed = cell_metrics()
    assert sorted(m["name"] for m in listed) == sorted(
        HOST_METRICS + DEVICE_METRICS)
    assert len(listed) <= 18
    assert not [m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", ()) and m not in listed]
    layers_named = {m["layer"] for m in bench["per_layer"]
                    if CELL not in m.get("workloads", ())}
    assert {m["layer"] for m in listed} <= layers_named
    assert {m["moves"] for m in listed} == {"ingest_entries_per_s"}
    for m in listed:
        spec = layer_file(m["name"])
        assert os.path.isfile(os.path.join(
            BENCH, "readers", spec["reader"] + ".py")), m["name"]
    for short, other in SAME_FILE.items():
        assert layer_file("qshard4." + short) == layer_file(other), short
    _entry, config = config_of(bench, CONFIG)
    assert layer_file("qshard4.step_device_ns_per_entry")["params"][
        "lanes_per_call"] == int(config["directives"]["batchSize"]) // CHIPS
    # The one roofline has the reader that knows a plane's call moves a
    # shard; the one-chip cell's reader would read four times the truth.
    roof = layer_file("qshard4.snapshot_copy_roofline")
    assert roof == dict(layer_file("snapshot_copy_roofline"),
                        reader="shard_copy_roofline")
    assert next(m for m in listed if m["name"].endswith("_roofline"))[
        "unit"] == "%"


def four_plane_profile(calls: int, seconds_a_call: float) -> dict:
    """A profile of four device planes as ``tracing.load_xplane`` gives
    it: every plane ran the replica copy ``calls`` times beside a mesh
    step, inside a window of ten seconds."""
    plane = {"ops": [], "modules": []}
    for k in range(calls):
        start = 1.0 + 1.5 * k
        plane["modules"].append(("jit_snapshot_copy(7)", start,
                                 seconds_a_call))
        plane["ops"].append(("copy.1", start, seconds_a_call))
        plane["modules"].append(("jit__local_step(9)", start + 0.5, 0.01))
        plane["ops"].append(("while.109", start + 0.5, 0.01))
    return {"devices": {f"/device:TPU:{i}": plane for i in range(CHIPS)},
            "lines": []}


def test_the_shard_reader_reads_a_quarter_of_the_table_readers_bytes_a_call():
    """Six copies on each of four planes, 6.6 ms each (a shard of 2^26
    slots at 80% of 819 GB/s): 24 calls. ``copy_roofline`` would charge
    each the whole table, 2^28 slots, and read four times the truth."""
    reduced = tracing.reduce_trace(four_plane_profile(6, 0.0066), 0.0, 10.0,
                                   {})
    assert reduced["module_calls"]["jit_snapshot_copy(7)"] == 6 * CHIPS
    _entry, config = config_of(bench_json(), CONFIG)
    ctx = {"trace": reduced, "config": config,
           "device": {"kind": "TPU v5 lite"}}
    params = layer_file("qshard4.snapshot_copy_roofline")["params"]
    shard = shard_copy_roofline.read(params, ctx)
    table = copy_roofline.read(params, ctx)
    assert shard == pytest.approx(table / CHIPS)
    assert shard == pytest.approx(
        100 * 24 * 2 * 2**26 * 32 / 819e9 / (24 * 0.0066))
    assert 79.0 < shard < 80.0 and table > 105.0
    assert shard_copy_roofline.shards(config) == CHIPS
    # One chip (no meshShape): what copy_roofline reads.
    _e, one = config_of(bench_json(), "icarus-serve-1chip")
    one_ctx = dict(ctx, config=one)
    assert shard_copy_roofline.shards(one) == 1
    assert shard_copy_roofline.read(params, one_ctx) \
        == copy_roofline.read(params, one_ctx)
    # No copy inside the window: nothing to read, never a zero.
    none = dict(ctx, trace=dict(reduced, modules={"jit__local_step(9)": 1.0},
                                module_calls={"jit__local_step(9)": 24}))
    assert shard_copy_roofline.read(params, none) is None
    with pytest.raises(KeyError):
        shard_copy_roofline.read(params, dict(ctx, device={"kind": "other"}))


def test_counter_ratio_divides_what_the_program_counts():
    out = {"t_open": 8.0, "t_durable": 30.0, "t_first": 10.0,
           "t_folded": 20.0}
    said = [(t, key, v) for t in (9.0, 11.0, 13.0, 16.0, 21.0)
            for key, v in (("qshard.batches", 1.0),
                           ("qshard.device_calls", 1.0),
                           ("qshard.lanes", 2.0),
                           ("qshard.padded_lanes", 62.0))]
    ctx = {"out": dict(out, counters=said)}
    calls = layer_file("qshard4.device_calls_per_batch")
    padded = layer_file("qshard4.padded_lane_share")
    assert calls["reader"] == padded["reader"] == "counter_ratio"
    assert counter_ratio.read(calls["params"], ctx) == 1.0
    assert counter_ratio.read(padded["params"], ctx) \
        == pytest.approx(100 * 62 / 64)
    # The per-shard loop it replaced: a call a shard hit.
    loop = dict(ctx, out=dict(out, counters=said + [
        (12.0, "qshard.device_calls", 2.0)]))
    assert counter_ratio.read(calls["params"], loop) == pytest.approx(5 / 3)
    # A program that counts none of them: left out. One that counts and
    # had no batch in the window: nothing to read.
    assert counter_ratio.read(calls["params"],
                              {"out": dict(out, counters=[])}) is ABSENT
    assert counter_ratio.read(calls["params"], {"out": dict(
        out, counters=[(t, k, v) for t, k, v in said
                       if k != "qshard.batches"])}) is ABSENT
    assert counter_ratio.read(calls["params"], {"out": dict(
        out, counters=[(25.0, k, v) for _t, k, v in said])}) is None


def test_a_program_without_the_family_leaves_four_out_by_name():
    """``layers.read_metrics`` over the cell's metrics that read the
    ring and the counters: a program from before the ``qshard.`` family
    (the parent of the PR that brought the cell, whose views probed a
    shard at a time) leaves out, by name, exactly what hangs on it, and
    fails nothing."""
    from test_span_ring import ctx_of, span

    ring = [span("serve.batch", 11.0 + k, 0.004, 10 + k, tid=3, lanes=1)
            for k in range(4)]
    ring += [span("serve.snapshot", 12.0, 0.03, 30, tid=4),
             span("snapshot.locked", 12.0, 0.008, 31, tid=4, parent=30),
             span("sink.queue_wait", 13.0, 0.05, 40, tid=1)]
    mine = [m for m in cell_metrics() if m["name"] in QSHARD_FAMILY + (
        "qshard4.snapshot_ms", "qshard4.snapshot_locked_ms",
        "qshard4.batcher_busy_share", "qshard4.sink_starved_share")]
    old = ctx_of(ring, t_open=8.0, counters=[(9.0, "serve.batches", 1.0)])
    metrics, absent = layers.read_metrics(mine, CELL, old, strict=True)
    assert sorted(absent) == sorted(QSHARD_FAMILY)
    assert sorted(metrics) == sorted([
        "qshard4.snapshot_ms", "qshard4.snapshot_locked_ms",
        "qshard4.batcher_busy_share", "qshard4.sink_starved_share"])
    new = ctx_of(ring + [
        span("qshard.probe", 11.0 + k, 0.002, 50 + k, tid=3, parent=10 + k,
             lanes=1, shards=1, width=16, replica=k % 2) for k in range(4)],
        t_open=8.0, counters=[
            (11.0 + k, key, v) for k in range(4) for key, v in (
                ("qshard.batches", 1.0), ("qshard.device_calls", 1.0),
                ("qshard.lanes", 1.0), ("qshard.padded_lanes", 63.0),
                ("qshard.host_lane_hits", 0.0))])
    metrics, absent = layers.read_metrics(mine, CELL, new, strict=True)
    assert absent == []
    values = {k: v["value"] for k, v in metrics.items()}
    assert values["qshard4.probe_ms"] == pytest.approx(2.0)
    assert values["qshard4.device_calls_per_batch"] == 1.0
    assert values["qshard4.shards_per_batch"] == 1.0
    assert values["qshard4.padded_lane_share"] == pytest.approx(100 * 63 / 64)


def test_the_committed_cell_is_correct_and_every_host_metric_reads():
    """The committed files at the tiny cut, traced, on a mesh of four:
    ``correct`` (every answer as the fixture has it, no program compiled
    in the round), no entry and no request failed; every batch of
    queries cost one device call; every metric read on the host has a
    number and the four read off the chips are left out, not failed."""
    lines = rehearse_cell("31370", "trace")
    line = lines[-1]
    assert line["correct"] is True, line["not_ok"]
    assert line["device"]["count"] == CHIPS
    assert line["by_generator"]["log_replay"] == {
        "attempted": BATCHES * 1024, "failed": 0}
    asked = line["by_generator"]["query_poisson"]
    assert asked["attempted"] > 20 and asked["failed"] == 0
    metrics = next(x for x in lines if isinstance(x, list))[0]
    assert sorted(metrics) == sorted(HOST_METRICS)
    values = {k: v["value"] for k, v in metrics.items()}
    assert all(isinstance(v, float) for v in values.values())
    assert values["qshard4.device_calls_per_batch"] == 1.0
    assert 1.0 <= values["qshard4.shards_per_batch"] <= CHIPS
    assert 0.0 < values["qshard4.padded_lane_share"] < 100.0
    assert values["qshard4.query_failed"] == 0.0
    for name in set(HOST_METRICS) - {"qshard4.query_failed"}:
        assert values[name] > 0.0, name
    assert not any("absent" in x for x in lines if isinstance(x, dict))


def test_a_wrong_answer_in_the_committed_cell_is_not_correct():
    """The control above the routing (``MembershipOracle.query_raw``
    answers the opposite): it holds on the mesh unedited."""
    line = rehearse_cell("31371", "wrong_answer")[-1]
    assert line["correct"] is False
    assert any("contradict the fixture" in what for what in line["not_ok"])
    assert line["by_generator"]["query_poisson"]["failed"] > 0
