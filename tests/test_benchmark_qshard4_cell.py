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


def test_every_metric_of_the_cell_names_a_reader_that_exists(  # noqa: F811
        monkeypatch):
    """Theirs, for a ``BENCHMARK.json`` that has grown since (theirs
    lets no metric outside the cell's own eighteen list the cell; a PR
    that lists one after them may edit no file under ``benchmark/``:
    ROADMAP R0): theirs sees the list as it stood when the block ended
    it, and what came after lists this cell beside the one-chip query
    cell."""
    whole = theirs.bench_json()
    names = [m["name"] for m in whole["per_layer"]]
    cut = max(i for i, n in enumerate(names) if n.startswith("qshard4.")) + 1
    monkeypatch.setattr(
        theirs, "bench_json",
        lambda: dict(whole, per_layer=whole["per_layer"][:cut]))
    theirs.test_every_metric_of_the_cell_names_a_reader_that_exists()
    assert names[cut:] == [FP_FALLBACK]
    assert whole["per_layer"][cut]["workloads"] == [
        "backfill-1log-query", theirs.CELL]
    assert whole["per_layer"][cut]["layer"] == "query plane"


def test_the_committed_cell_is_correct_and_every_host_metric_reads(  # noqa: F811
        shared_metrics_aside):
    """Theirs, with the one metric of several cells that lists this
    cell read apart: every lane the batcher fingerprinted in the round
    took the native call."""
    theirs.test_the_committed_cell_is_correct_and_every_host_metric_reads()
    assert shared_metrics_aside == {FP_FALLBACK: 0.0}


pytestmark = [pytest.mark.timeout(300),
              pytest.mark.usefixtures("benchmark_checkout")]
