"""The tests of the cell ``backfill-3log``
(``benchmark/tests/test_multilog_cell.py``) as tier-1 tests; see
``test_benchmark_harness.py``. Two of them are whole rehearsals of the
committed cell at a tiny table.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.tests import test_multilog_cell as theirs  # noqa: E402
from benchmark.tests.test_multilog_cell import *  # noqa: E402,F401,F403


def test_the_cell_lists_what_the_issue_names(monkeypatch):  # noqa: F811
    """Theirs, for a ``BENCHMARK.json`` that has grown since (theirs
    holds the cell's sixteen to the END of the list, and a PR that lists
    a metric after them may edit no file under ``benchmark/``: ROADMAP
    R0): the sixteen are one block, and what a later PR lists comes
    after it and is not the cell's alone."""
    whole = theirs.bench_json()
    last = max(i for i, m in enumerate(whole["per_layer"])
               if m.get("workloads") == [theirs.CELL])
    then = dict(whole, per_layer=whole["per_layer"][:last + 1])
    monkeypatch.setattr(theirs, "bench_json", lambda: then)
    theirs.test_the_cell_lists_what_the_issue_names()


def test_the_committed_cell_is_correct_and_every_host_metric_reads(  # noqa: F811
        shared_metrics_aside):
    """Theirs, with the one metric that several cells list since PR 37
    read apart: no lane of the cell's folds took the NumPy routine."""
    theirs.test_the_committed_cell_is_correct_and_every_host_metric_reads()
    assert shared_metrics_aside == {"fold.meta_fallback_lanes": 0.0}


pytestmark = [pytest.mark.timeout(300),
              pytest.mark.usefixtures("benchmark_checkout")]
