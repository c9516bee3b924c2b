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
from benchmark.tests.test_threads import GIL_METRICS  # noqa: E402


def test_the_cell_lists_what_the_issue_names(own_blocks_only):  # noqa: F811
    """Theirs, for a ``BENCHMARK.json`` that has grown since (theirs
    holds the cell's sixteen to the END of the list, and a PR that lists
    a metric after them may edit no file under ``benchmark/``: ROADMAP
    R0): the sixteen end the cells' own blocks, and what a later PR
    lists comes after the first metric of several cells."""
    theirs.test_the_cell_lists_what_the_issue_names()


def test_the_committed_cell_is_correct_and_every_host_metric_reads(  # noqa: F811
        shared_metrics_aside):
    """Theirs, with the metrics listed after the cells' own blocks read
    apart: no lane of the cell's folds took the NumPy routine (PR 37),
    and each of PR 38's five that list this cell has a number."""
    theirs.test_the_committed_cell_is_correct_and_every_host_metric_reads()
    assert shared_metrics_aside.pop("fold.meta_fallback_lanes") == 0.0
    assert shared_metrics_aside.pop("decode.pages_walked") == 0.0  # PR 39
    assert shared_metrics_aside.pop("ckpt.unpacked_saves") == 0.0  # PR 42
    assert sorted(shared_metrics_aside) == sorted(GIL_METRICS[:5])
    assert all(v >= 0.0 for v in shared_metrics_aside.values())


pytestmark = [pytest.mark.timeout(300),
              pytest.mark.usefixtures("benchmark_checkout")]
