"""The tests of the cell ``backfill-3log-query-shard4``
(``benchmark/tests/test_qshard4_cell.py``) as tier-1 tests; see
``test_benchmark_harness.py``. Two of them are whole rehearsals of the
committed cell at a tiny table, on a mesh of four of the CPU's virtual
devices (the rehearsal's child asks for them itself: the fixture takes
the suite's eight away for the one-chip cells' sake).
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.tests import test_qshard4_cell as theirs  # noqa: E402,F401
from benchmark.tests.test_qshard4_cell import *  # noqa: E402,F401,F403

FP_FALLBACK = "fp.fallback_lanes"  # PR 44: both query cells, this one too
FRONT_CPU = "qshard4.front_cpu_ms_per_request"  # PR 45: this cell's alone
POOL_REQUESTS = "front.pool_requests"  # PR 45: both query cells


def test_every_metric_of_the_cell_names_a_reader_that_exists(  # noqa: F811
        monkeypatch):
    """Theirs, for a ``BENCHMARK.json`` that has grown since (theirs
    lets no metric outside the cell's own eighteen list the cell; a PR
    that lists one after them may edit no file under ``benchmark/``:
    ROADMAP R0): theirs sees the list as it stood when the block ended
    it, and what came after lists this cell beside the one-chip query
    cell, but for the front's CPU a request here (PR 45), which reads
    ``front.cpu_ms_per_request``'s span with its parameters and is of
    the same layer."""
    whole = theirs.bench_json()
    names = [m["name"] for m in whole["per_layer"]]
    cut = names.index(FP_FALLBACK)
    assert names[cut - 1].startswith("qshard4.")
    monkeypatch.setattr(
        theirs, "bench_json",
        lambda: dict(whole, per_layer=whole["per_layer"][:cut]))
    theirs.test_every_metric_of_the_cell_names_a_reader_that_exists()
    assert names[cut:] == [FP_FALLBACK, FRONT_CPU, POOL_REQUESTS]
    after = {m["name"]: m for m in whole["per_layer"][cut:]}
    for name in (FP_FALLBACK, POOL_REQUESTS):
        assert after[name]["workloads"] == ["backfill-1log-query",
                                            theirs.CELL]
        assert after[name]["layer"] == "query plane"
        assert after[name]["source"] == "program_counter"
    assert after[FRONT_CPU]["workloads"] == [theirs.CELL]
    one_chip = next(m for m in whole["per_layer"]
                    if m["name"] == "front.cpu_ms_per_request")
    assert {k: after[FRONT_CPU][k] for k in after[FRONT_CPU]
            if k not in ("name", "workloads")} \
        == {k: one_chip[k] for k in one_chip if k not in ("name", "workloads")}
    assert theirs.layer_file(FRONT_CPU) \
        == theirs.layer_file("front.cpu_ms_per_request") == {
            "reader": "span_cpu",
            "params": {"span": "front.conn", "take": "tdur",
                       "per": "arg:requests", "scale": 0.001}}
    assert theirs.layer_file(POOL_REQUESTS) == {
        "reader": "counter_sum",
        "params": {"key": "front.pool_requests", "phase": "round"}}


def test_the_committed_cell_is_correct_and_every_host_metric_reads(  # noqa: F811
        shared_metrics_aside):
    """Theirs, with the metrics after the cell's block that list this
    cell read apart: every lane the batcher fingerprinted in the round
    took the native call, the front's one thread spent CPU on every
    connection, and no request left its loop for the pool."""
    theirs.test_the_committed_cell_is_correct_and_every_host_metric_reads()
    assert shared_metrics_aside.pop(FRONT_CPU) > 0.0
    assert shared_metrics_aside == {FP_FALLBACK: 0.0, POOL_REQUESTS: 0.0}


pytestmark = [pytest.mark.timeout(300),
              pytest.mark.usefixtures("benchmark_checkout")]
