"""Tests of the cell ``backfill-1log-query`` (configuration
``icarus-serve-1chip``): its committed files through a whole run at a
rehearsal's size, its control, and the readers of its per-layer
metrics:  python3 -m pytest benchmark/tests -q

The reference the answers are held to is what the repository keeps
apart from the program: ``fixture.py``'s arithmetic (which serial and
issuer entry ``i`` of log ``k`` carries, which were never fed) and
``query_poisson.summarise``.
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
import snapshot_cost  # noqa: E402
from readers import copy_roofline, device_time, harness_number  # noqa: E402
from test_span_ring import ctx_of, span  # noqa: E402

CELL = "backfill-1log-query"
# Read on the host: a rehearsal on the CPU has a number for each.
HOST_METRICS = (
    "serve.client_p50_ms", "serve.client_p95_ms", "serve.client_p99_ms",
    "serve.query_failed", "serve.wait_ms", "serve.contains_device_ms",
    "serve.snapshot_ms", "serve.batcher_busy_share", "serve.snapshot_share",
    "snapshot.locked_ms", "snapshot.host_mb_per_refresh",
    "serve.fold_us_per_entry", "serve.fetch_http_us_per_entry")
# Read off the chip: checked below on numbers written by hand.
DEVICE_METRICS = ("serve.peak_hbm_gb", "serve.device_idle_pct",
                  "snapshot_copy_roofline")


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
        "icarus-serve-1chip", CELL, 1)
    assert sorted(m["name"] for m in cell_metrics()) == sorted(
        HOST_METRICS + DEVICE_METRICS)
    assert all(m["moves"] == "ingest_entries_per_s" for m in cell_metrics())
    with open(os.path.join(BENCH, "traffic", CELL + ".json")) as fh:
        replay, queries = json.load(fh)["generators"]
    with open(os.path.join(BENCH, "traffic", "backfill-1log.json")) as fh:
        alone = json.load(fh)["generators"][0]
    # The stream is backfill-1log's but for the window's length.
    assert {k: v for k, v in replay.items()
            if k != "window_entries_per_second"} == {
        k: v for k, v in alone.items() if k != "window_entries_per_second"}
    assert queries["kind"] == "query_poisson" and queries["port"] == "queryPort"
    with open(os.path.join(BENCH, "configs", "icarus-serve-1chip.json")) as fh:
        config = json.load(fh)
    with open(os.path.join(BENCH, "configs", "icarus-dedup-1chip.json")) as fh:
        dedup = json.load(fh)
    assert config["ports"] == ["queryPort"]
    assert config["directives"] == dict(
        dedup["directives"], serveReplicas=2, serveDevice=True)
    assert set(dedup["guarantees"]) < set(config["guarantees"])


def test_the_committed_cell_is_correct_and_every_host_metric_reads():
    """The committed files at the tiny cut, traced: ``correct``, no
    entry failed, and each of the cell's metrics that is read on the
    host has a number; the three read off the chip have nothing to read
    on the CPU and are left out, not failed. Alone on a machine no
    request fails either; among a test suite's other workers one may
    wait out its ten seconds (3 of 222 did once under six), which is
    that machine's doing, so the test allows a tenth: a query plane
    that refused or broke its requests would fail them all, and one
    wrong answer fails ``correct``. On the chip the cell's own runs read
    0 (PERF.md)."""
    lines = rehearse_cell("31350", "trace")
    line = lines[-1]
    assert line["correct"] is True, line["not_ok"]
    parts = line["by_generator"]
    assert list(parts) == ["log_replay", "query_poisson"]
    assert parts["log_replay"] == {"attempted": 64 * 1024, "failed": 0}
    asked = parts["query_poisson"]["attempted"]
    assert asked > 50
    assert parts["query_poisson"]["failed"] <= asked // 10
    metrics = next(x for x in lines if isinstance(x, list))[0]
    assert sorted(metrics) == sorted(HOST_METRICS)
    for name in HOST_METRICS:
        assert isinstance(metrics[name]["value"], float), name
    assert metrics["snapshot.host_mb_per_refresh"]["value"] == 0.0
    assert metrics["serve.query_failed"]["value"] \
        == parts["query_poisson"]["failed"]
    for name in set(HOST_METRICS) - {"snapshot.host_mb_per_refresh",
                                     "serve.query_failed"}:
        assert metrics[name]["value"] > 0.0, name
    assert metrics["snapshot.locked_ms"]["value"] \
        < metrics["serve.snapshot_ms"]["value"]
    assert not any("absent" in x for x in lines if isinstance(x, dict))


def test_wrong_answer_in_the_committed_cell_is_not_correct():
    line = rehearse_cell("31351", "wrong_answer")[-1]
    assert line["correct"] is False
    assert [w[:40] for w in line["not_ok"]] == [
        "query_poisson: answers that contradict t"]
    assert line["by_generator"]["query_poisson"]["failed"] > 0
    assert line["by_generator"]["log_replay"]["failed"] == 0


# A window of ten seconds (10 to 20 on the run's clock) of the query
# plane's spans as the program records them: two requests, one batch,
# two captures, the second ending after the window.
SERVE = [
    span("serve.wait", 11.0, 0.010, 1, tid=4, lanes=1),
    span("serve.wait", 12.0, 0.030, 2, tid=5, lanes=1),
    span("serve.batch", 12.0, 0.5, 3, tid=6, lanes=2, requests=2, epoch=7),
    span("serve.lookup", 12.1, 0.3, 4, parent=3, tid=6, lanes=2, epoch=7),
    span("serve.contains_device", 12.2, 0.004, 5, parent=4, tid=6, lanes=2),
    span("serve.snapshot", 14.0, 1.0, 6, tid=7, epoch=8),
    span("serve.snapshot", 19.5, 1.0, 7, tid=7, epoch=9),
]
SNAPSHOT = [
    span("snapshot.capture", 14.0, 1.0, 8, parent=6, tid=7, epoch=8,
         replica=0, through_entries=4096, host_bytes=0),
    span("snapshot.locked", 14.25, 0.5, 9, parent=8, tid=7),
    span("snapshot.wait_copy", 14.75, 0.25, 10, parent=8, tid=7),
    span("snapshot.capture", 19.5, 1.0, 11, parent=7, tid=7, epoch=9,
         replica=1, through_entries=8192, host_bytes=6_000_000),
    span("snapshot.locked", 19.5, 0.125, 12, parent=11, tid=7),
]
# The cell's metrics that read these spans from the program's ring.
RING_METRICS = (
    "serve.wait_ms", "serve.contains_device_ms", "serve.snapshot_ms",
    "serve.batcher_busy_share", "serve.snapshot_share",
    "snapshot.locked_ms", "snapshot.host_mb_per_refresh")


def read_ring(events, **out) -> tuple[dict, list[str]]:
    mine = [m for m in cell_metrics() if m["name"] in RING_METRICS]
    metrics, absent = layers.read_metrics(mine, CELL, ctx_of(events, **out),
                                          strict=False)
    return {k: v["value"] for k, v in metrics.items()}, absent


def test_the_ring_readers_on_spans_written_by_hand():
    got, absent = read_ring(SERVE + SNAPSHOT)
    assert absent == []
    assert got == pytest.approx({
        "serve.wait_ms": 20.0,              # two spans: 10 and 30 ms
        "serve.contains_device_ms": 4.0,
        "serve.snapshot_ms": 1000.0,        # the one that ENDED inside
        "serve.batcher_busy_share": 5.0,    # 0.5 s of ten
        "serve.snapshot_share": 15.0,       # 1.0 s and the 0.5 s inside
        "snapshot.locked_ms": 312.5,        # 500 and 125 ms
        "snapshot.host_mb_per_refresh": 0.0})
    # A capture that took the table over the host link, as PR 28's parent
    # did: it shows, per refresh that ended in the window.
    got, _ = read_ring(SERVE + SNAPSHOT, t_folded=21.0)
    assert got["snapshot.host_mb_per_refresh"] == pytest.approx(3.0)
    assert got["serve.snapshot_ms"] == pytest.approx(1000.0)


def test_a_program_without_the_snapshot_family_reads_absent_for_it():
    """The parent of the PR that brought the cell records ``serve.``
    spans and none of the family ``snapshot.``: its metrics are left out
    by name and the ``serve.`` ones read as they do on the change."""
    got, absent = read_ring(SERVE)
    assert sorted(absent) == ["snapshot.host_mb_per_refresh",
                              "snapshot.locked_ms"]
    assert got == pytest.approx({
        "serve.wait_ms": 20.0, "serve.contains_device_ms": 4.0,
        "serve.snapshot_ms": 1000.0, "serve.batcher_busy_share": 5.0,
        "serve.snapshot_share": 15.0})
    # The family is there and the span is not, or has lost its argument
    # (a span renamed): nothing to read, which fails a listed metric.
    got, absent = read_ring(SERVE + [e for e in SNAPSHOT
                                     if e["name"] != "snapshot.locked"])
    assert absent == [] and "snapshot.locked_ms" not in got
    bare = [dict(e, args={"epoch": 8}) if e["name"] == "snapshot.capture"
            else e for e in SNAPSHOT]
    got, absent = read_ring(SERVE + bare)
    assert absent == [] and "snapshot.host_mb_per_refresh" not in got
    # A ring that forgot events and holds none from before the window
    # may have forgotten some inside it: nothing to read.
    metrics, absent = layers.read_metrics(
        [m for m in cell_metrics() if m["name"] == "snapshot.locked_ms"],
        CELL, ctx_of(SERVE + SNAPSHOT, dropped=5), strict=False)
    assert metrics == {} and absent == []


def test_the_copy_costs_the_table_read_once_and_written_once():
    with open(os.path.join(BENCH, "configs", "icarus-serve-1chip.json")) as fh:
        bits = json.load(fh)["directives"]["tableBits"]
    assert snapshot_cost.table_copy(bits) == {"hbm_bytes": 2 * 2**26 * 32}


def test_the_device_metrics_on_a_trace_written_by_hand():
    """Fifty copies of a 2^26-slot table in 0.4 s of device time: 4.29
    GB each at 819 GB/s is 5.24 ms, so 65.5% of the roofline; the step's
    modules do not count, and a device that is not in the table of
    peaks is an error."""
    trace = {"busy_s": 1.5, "window_s": 25.0,
             "modules": {"jit_snapshot_copy(123)": 0.4,
                         "jit_ingest_core(9)": 1.0, "jit_contains(4)": 0.1},
             "module_calls": {"jit_snapshot_copy(123)": 50,
                              "jit_ingest_core(9)": 48, "jit_contains(4)": 2000}}
    ctx = {"trace": trace, "config": {"directives": {"tableBits": 26}},
           "device": {"kind": "TPU v5 lite", "memory_peak_bytes": 9_080_000_000},
           "values": {}, "out": {}}
    assert layer_file("snapshot_copy_roofline")["reader"] == "copy_roofline"
    roof = copy_roofline.read(layer_file("snapshot_copy_roofline")["params"],
                              ctx)
    assert roof == pytest.approx(100 * 50 * 2**32 / 819e9 / 0.4)
    assert 65.0 < roof < 66.0
    assert device_time.read(layer_file("serve.device_idle_pct")["params"],
                            ctx) == pytest.approx(94.0)
    assert harness_number.read(layer_file("serve.peak_hbm_gb")["params"],
                               ctx) == pytest.approx(9.08)
    # No copy inside the window: nothing to read, never a zero.
    none = dict(ctx, trace=dict(trace, modules={"jit_ingest_core(9)": 1.0},
                                module_calls={"jit_ingest_core(9)": 48}))
    assert copy_roofline.read({"match": "snapshot_copy"}, none) is None
    with pytest.raises(KeyError):
        copy_roofline.read({"match": "snapshot_copy"},
                           dict(ctx, device={"kind": "some other chip"}))
