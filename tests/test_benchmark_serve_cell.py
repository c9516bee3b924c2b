"""The tests of the cell ``backfill-1log-query``
(``benchmark/tests/test_serve_cell.py``) as tier-1 tests; see
``test_benchmark_harness.py``. Two of them are whole rehearsals of the
committed cell, 40 s of a run's own clock each.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.tests import test_serve_cell as theirs  # noqa: E402
from benchmark.tests.test_serve_cell import *  # noqa: E402,F401,F403
from benchmark.tests.test_threads import GIL_METRICS  # noqa: E402


def test_the_cell_lists_what_the_issue_names(own_blocks_only):  # noqa: F811
    """Theirs, for a ``BENCHMARK.json`` that has grown since (theirs
    takes every metric that lists this cell alone for the cell's own
    sixteen, and PR 38 listed two more of them after the metrics of
    several cells; it may edit no file under ``benchmark/``: ROADMAP
    R0): the sixteen are what stands before the first shared metric."""
    theirs.test_the_cell_lists_what_the_issue_names()


def test_the_committed_cell_is_correct_and_every_host_metric_reads(  # noqa: F811
        shared_metrics_aside):
    """Theirs, with the metrics listed after the cells' own blocks read
    apart: no lane of the cell's folds took the NumPy routine (PR 37),
    no lane of its lookups' fingerprints either (PR 44), no request
    left the front's loop for its pool (PR 45), and each of PR 38's
    seven has a number."""
    theirs.test_the_committed_cell_is_correct_and_every_host_metric_reads()
    assert shared_metrics_aside.pop("fold.meta_fallback_lanes") == 0.0
    assert shared_metrics_aside.pop("decode.pages_walked") == 0.0  # PR 39
    assert shared_metrics_aside.pop("ckpt.unpacked_saves") == 0.0  # PR 42
    assert shared_metrics_aside.pop("fp.fallback_lanes") == 0.0  # PR 44
    assert shared_metrics_aside.pop("front.pool_requests") == 0.0  # PR 45
    assert sorted(shared_metrics_aside) == sorted(GIL_METRICS)
    assert all(v >= 0.0 for v in shared_metrics_aside.values())
    assert shared_metrics_aside["front.cpu_ms_per_request"] > 0.0


pytestmark = [pytest.mark.timeout(300),
              pytest.mark.usefixtures("benchmark_checkout")]
