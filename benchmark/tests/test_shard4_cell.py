"""Tests of the cell ``backfill-3log-shard4`` (configuration
``loglist3-shard4``): its committed files against the one-chip control's,
a whole run of them at a rehearsal's size on four of the CPU's virtual
devices, its control, and the two readers its per-layer metrics brought
(``gauge_spread``, ``counter_per``):
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
from readers import counter_per, gauge_spread  # noqa: E402

CELL = "backfill-3log-shard4"
CONTROL = "backfill-3log"
CHIPS = 4
# Read on the host: a rehearsal on the CPU has a number for each.
HOST_METRICS = (
    "shard4.put_ms_per_batch", "shard4.row_h2d_mb_per_batch",
    "shard4.row_d2h_mb_per_batch", "shard4.dispatch_spill_lanes",
    "shard4.fill_skew_pct", "shard4.full_saves", "shard4.drain_s",
    "shard4.ckpt_d2h_s", "shard4.compile_programs",
    "shard4.decode_ns_per_entry", "shard4.fold_us_per_entry",
    "shard4.fetch_http_us_per_entry", "shard4.sink_starved_share",
    "shard4.fetch_blocked_share", "shard4.loadgen_headroom_x")
# Read off the chips: nothing to read on the CPU.
DEVICE_METRICS = (
    "shard4.step_device_ns_per_entry", "shard4.all_to_all_ns_per_entry",
    "shard4.psum_us_per_step", "shard4.sha256_roofline",
    "shard4.device_idle_pct", "shard4.peak_hbm_gb")
# What hangs on the program's ``shard.`` family: a program from before it
# leaves these out of a traced line, by name, and reads the rest.
SHARD_FAMILY = (
    "shard4.put_ms_per_batch", "shard4.row_h2d_mb_per_batch",
    "shard4.row_d2h_mb_per_batch", "shard4.dispatch_spill_lanes",
    "shard4.fill_skew_pct")
BATCHES = 60  # the window at --seconds 35, as the control's
TINY_ROW_BYTES = 1024 * 2048  # a rehearsal's batch of 2,048-byte rows


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


def test_the_cell_is_the_control_on_a_mesh_and_nothing_else():
    bench = bench_json()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    control = next(w for w in bench["workloads"] if w["name"] == CONTROL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "loglist3-shard4", control["traffic"], CHIPS)
    assert sum(w["chips"] == CHIPS for w in bench["workloads"]) == 1
    entry, config = config_of(bench, "loglist3-shard4")
    one_entry, one = config_of(bench, control["config"])
    mine = dict(config["directives"])
    assert mine.pop("meshShape") == f"shard:{CHIPS}"
    assert mine == one["directives"] and "meshShape" not in one["directives"]
    assert (config["chips"], one["chips"]) == (CHIPS, 1)
    added = dict(config["guarantees"])
    assert added.pop("placement") and added.pop("topology")
    assert added == one["guarantees"]  # the six, word for word
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) \
        == sorted(one["reduced"]) == sorted(one_entry["reduced"])
    assert {k for k in config["reduced"]
            if config["reduced"][k] != one["reduced"][k]} \
        == {"chips", "tableBits"}
    assert config["source"] == entry["source"] != one["source"]
    assert sorted(config) == sorted(one)


def test_every_metric_of_the_cell_names_a_reader_that_exists():
    bench = bench_json()
    listed = cell_metrics()
    assert sorted(m["name"] for m in listed) == sorted(
        HOST_METRICS + DEVICE_METRICS)
    assert [m["name"] for m in bench["per_layer"][-len(listed):]] \
        == [m["name"] for m in listed]  # at the end of the list
    assert not [m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", ()) and m not in listed]
    layers_named = {m["layer"] for m in bench["per_layer"]
                    if CELL not in m.get("workloads", ())}
    assert {m["layer"] for m in listed} <= layers_named
    _entry, config = config_of(bench, "loglist3-shard4")
    a_chip = int(config["directives"]["batchSize"]) // CHIPS
    lanes = {}
    for m in listed:
        spec = layer_file(m["name"])
        assert os.path.isfile(os.path.join(
            BENCH, "readers", spec["reader"] + ".py")), m["name"]
        if "lanes_per_call" in spec.get("params", {}):
            lanes[m["name"]] = spec["params"]["lanes_per_call"]
    # The profile sums four planes, so a call is a chip's quarter of the
    # batch (``batchSize`` would read four times the truth); the psum is
    # read per call.
    assert lanes == {"shard4.step_device_ns_per_entry": a_chip,
                     "shard4.all_to_all_ns_per_entry": a_chip,
                     "shard4.sha256_roofline": a_chip,
                     "shard4.psum_us_per_step": 1}
    # The control's host readers under this cell's names, to the letter.
    for short in ("decode_ns_per_entry", "fold_us_per_entry",
                  "fetch_http_us_per_entry", "sink_starved_share",
                  "fetch_blocked_share", "loadgen_headroom_x", "full_saves",
                  "drain_s", "compile_programs", "device_idle_pct",
                  "peak_hbm_gb"):
        assert layer_file("shard4." + short) \
            == layer_file("multilog." + short), short


def test_the_committed_cell_is_correct_and_every_host_metric_reads():
    """The committed files at the tiny cut, traced, on a mesh of four:
    ``correct``, no entry failed; the round holds ONE full checkpoint;
    a batch's rows went to the device once and never came back; no lane
    spilled; every metric read on the host has a number and the six
    read off the chips are left out, not failed."""
    lines = rehearse_cell("31360", "trace")
    line = lines[-1]
    assert line["correct"] is True, line["not_ok"]
    assert line["device"]["count"] == CHIPS
    assert line["by_generator"] == {"log_replay": {
        "attempted": BATCHES * 1024, "failed": 0}}
    metrics = next(x for x in lines if isinstance(x, list))[0]
    assert sorted(metrics) == sorted(HOST_METRICS)
    values = {k: v["value"] for k, v in metrics.items()}
    assert all(isinstance(v, float) for v in values.values())
    assert values["shard4.full_saves"] == 1.0
    assert values["shard4.row_d2h_mb_per_batch"] == 0.0
    assert values["shard4.row_h2d_mb_per_batch"] \
        == pytest.approx(TINY_ROW_BYTES / 1e6)
    assert values["shard4.dispatch_spill_lanes"] == 0.0
    assert 0.0 <= values["shard4.fill_skew_pct"] < 25.0
    assert 0.0 < values["shard4.ckpt_d2h_s"] < values["shard4.drain_s"]
    for name in set(HOST_METRICS) - {"shard4.row_d2h_mb_per_batch",
                                     "shard4.dispatch_spill_lanes",
                                     "shard4.fill_skew_pct"}:
        assert values[name] > 0.0, name
    assert not any("absent" in x for x in lines if isinstance(x, dict))


def test_lost_entry_in_the_committed_cell_is_not_correct():
    line = rehearse_cell("31361", "lost_entry")[-1]
    assert line["correct"] is False
    assert "durable report: unique serials" in line["not_ok"]
    assert line["by_generator"]["log_replay"]["failed"] > 0


FILLS = {"shard.fill_min": 990.0, "shard.fill_max": 1010.0,
         "shard.fill_mean": 1000.0, "ingest.decode_threads": 4.0}


def test_gauge_spread_on_gauges_written_by_hand():
    spec = layer_file("shard4.fill_skew_pct")
    assert spec["reader"] == "gauge_spread"
    read = lambda gauges: gauge_spread.read(  # noqa: E731
        spec["params"], {"gauges": gauges})
    assert read(FILLS) == pytest.approx(2.0)
    assert read(dict(FILLS, **{"shard.fill_max": 990.0})) == 0.0
    # One chip sets none of the three, nor does a program from before
    # them: the metric is left out. Some and not all is a gauge renamed.
    assert read({"ingest.decode_threads": 4.0}) is ABSENT
    assert read({k: v for k, v in FILLS.items()
                 if k != "shard.fill_mean"}) is None
    assert read(dict(FILLS, **{"shard.fill_mean": 0.0})) is None


def test_counter_per_divides_what_counter_sum_sums():
    out = {"t_open": 8.0, "t_durable": 30.0, "t_first": 10.0,
           "t_folded": 20.0}
    puts = [(t, "shard.row_bytes_h2d", 2e6) for t in (9.0, 11.0, 13.0, 16.0,
                                                      19.5, 21.0)]
    ctx = {"out": dict(out, counters=puts + [(12.0, "other", 5.0)]),
           "batches": 4, "entries": 1000}
    assert counter_per.read(
        layer_file("shard4.row_h2d_mb_per_batch")["params"], ctx) \
        == pytest.approx(2.0)  # four puts inside the window, four batches
    assert counter_per.read({"key": "shard.row_bytes_h2d", "phase": "round",
                             "per": "entry"}, ctx) == pytest.approx(1.2e4)
    assert counter_per.read({"key": "shard.row_bytes_h2d"}, ctx) == 8e6
    # Every step says the counter, by 0: a reading. Never said: left out.
    zero = dict(ctx, out=dict(out, counters=[
        (t, "shard.row_bytes_d2h", 0.0) for t in (9.0, 12.0)]))
    spec = layer_file("shard4.row_d2h_mb_per_batch")
    assert spec["reader"] == "counter_per"
    assert counter_per.read(spec["params"], zero) == 0.0
    assert counter_per.read(spec["params"], ctx) is ABSENT


def test_a_program_without_the_family_leaves_five_out_by_name():
    """``layers.read_metrics`` over the cell's host metrics that read
    the ring, the counters and the gauges: a program from before the
    ``shard.`` family (the parent of the PR that brought the cell)
    leaves out, by name, exactly what hangs on it, and fails nothing."""
    from test_span_ring import ctx_of, span

    ring = [span("ckpt.save", 22.5, 6.0, 1, kind="full"),
            span("ckpt.d2h", 22.5, 1.0, 2, parent=1),
            span("mesh.step", 11.0, 0.01, 3, tid=2, shards=4)]
    mine = [m for m in cell_metrics() if m["name"] in SHARD_FAMILY + (
        "shard4.full_saves", "shard4.ckpt_d2h_s")]
    old = dict(ctx_of(ring, t_open=8.0, counters=[
        (9.0, "ingest.partial_batches", 0.0)]), gauges={})
    metrics, absent = layers.read_metrics(mine, CELL, old, strict=True)
    assert sorted(absent) == sorted(SHARD_FAMILY)
    assert {k: v["value"] for k, v in metrics.items()} == {
        "shard4.full_saves": 1.0, "shard4.ckpt_d2h_s": 1.0}
    new = dict(ctx_of(ring + [
        span("shard.put", 11.0 + k, 0.002, 10 + k, tid=2, bytes=2e6, shards=4)
        for k in range(4)], t_open=8.0, counters=[
        (t, key, v) for t in (11.0, 12.0, 13.0, 14.0) for key, v in (
            ("shard.row_bytes_h2d", 2e6), ("shard.row_bytes_d2h", 0.0),
            ("shard.dispatch_spill_lanes", 0.0))]), gauges=FILLS)
    metrics, absent = layers.read_metrics(mine, CELL, new, strict=True)
    assert absent == [] and {k: v["value"] for k, v in metrics.items()} == {
        "shard4.put_ms_per_batch": pytest.approx(2.0),
        "shard4.row_h2d_mb_per_batch": pytest.approx(2.0),
        "shard4.row_d2h_mb_per_batch": 0.0,
        "shard4.dispatch_spill_lanes": 0.0,
        "shard4.fill_skew_pct": pytest.approx(2.0),
        "shard4.full_saves": 1.0, "shard4.ckpt_d2h_s": 1.0}
