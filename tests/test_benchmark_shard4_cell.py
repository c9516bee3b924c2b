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

QUERY_CELL = "backfill-3log-query-shard4"  # PR 43: the second on four chips


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


pytestmark = [pytest.mark.timeout(300),
              pytest.mark.usefixtures("benchmark_checkout")]
