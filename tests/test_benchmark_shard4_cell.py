"""The tests of the cell ``backfill-3log-shard4``
(``benchmark/tests/test_shard4_cell.py``) as tier-1 tests; see
``test_benchmark_harness.py``. Two of them are whole rehearsals of the
committed cell at a tiny table, on a mesh of four of the CPU's virtual
devices (the rehearsal's child asks for them itself: the fixture takes
the suite's eight away for the one-chip cells' sake).
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.tests import test_shard4_cell as theirs  # noqa: E402
from benchmark.tests.test_shard4_cell import *  # noqa: E402,F401,F403

UNPACKED_SAVES = "ckpt.unpacked_saves"  # PR 42: all four cells, this one too
QUERY_CELL = "backfill-3log-query-shard4"  # PR 43: the second on four chips


def test_every_metric_of_the_cell_names_a_reader_that_exists(  # noqa: F811
        monkeypatch):
    """Theirs, for a ``BENCHMARK.json`` that has grown since (theirs
    holds the cell's block to the END of the list and lets no other
    metric list the cell; a PR that lists one after it may edit no file
    under ``benchmark/``: ROADMAP R0): theirs sees the list as it stood
    when the block ended it, and what came after lists this cell among
    several."""
    whole = theirs.bench_json()
    names = [m["name"] for m in whole["per_layer"]]
    cut = max(i for i, n in enumerate(names) if n.startswith("shard4.")) + 1
    monkeypatch.setattr(
        theirs, "bench_json",
        lambda: dict(whole, per_layer=whole["per_layer"][:cut]))
    theirs.test_every_metric_of_the_cell_names_a_reader_that_exists()
    assert names[cut] == UNPACKED_SAVES
    assert theirs.CELL in whole["per_layer"][cut]["workloads"]
    assert len(whole["per_layer"][cut]["workloads"]) == 4
    # Then the second four-chip cell's own block (PR 43), which lists
    # neither this cell nor any other; then the query cells' one (PR
    # 44) and the front's two (PR 45: the second four-chip cell's, and
    # the query cells'), none of which lists this cell: it has no
    # query plane.
    assert all(m["workloads"] == [QUERY_CELL] and m["name"].startswith(
        "qshard4.") for m in whole["per_layer"][cut + 1:-3])
    assert names[-3:] == ["fp.fallback_lanes",
                          "qshard4.front_cpu_ms_per_request",
                          "front.pool_requests"]
    assert all(theirs.CELL not in m["workloads"]
               for m in whole["per_layer"][-3:])


def test_the_cell_is_the_control_on_a_mesh_and_nothing_else(  # noqa: F811
        monkeypatch):
    """Theirs, for a ``BENCHMARK.json`` with a second cell on four chips
    (theirs counts one; PR 43 brought ``backfill-3log-query-shard4`` and
    may edit no file under ``benchmark/``): theirs sees the cells as
    they stood, and the two on four chips are half of the five, rounded
    down."""
    whole = theirs.bench_json()
    assert [w["name"] for w in whole["workloads"]
            if w["chips"] == theirs.CHIPS] == [theirs.CELL, QUERY_CELL]
    assert len(whole["workloads"]) // 2 >= 2
    monkeypatch.setattr(theirs, "bench_json", lambda: dict(
        whole, workloads=[w for w in whole["workloads"]
                          if w["name"] != QUERY_CELL]))
    theirs.test_the_cell_is_the_control_on_a_mesh_and_nothing_else()


def test_the_committed_cell_is_correct_and_every_host_metric_reads(  # noqa: F811
        shared_metrics_aside):
    """Theirs, with the one metric of several cells that lists this
    cell read apart: the round's full save was packed, shard by shard."""
    theirs.test_the_committed_cell_is_correct_and_every_host_metric_reads()
    assert shared_metrics_aside == {UNPACKED_SAVES: 0.0}


pytestmark = [pytest.mark.timeout(300),
              pytest.mark.usefixtures("benchmark_checkout")]
