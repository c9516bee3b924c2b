"""What ``BENCHMARK.json`` lists for the cell ``backfill-1log-loaded``
(two tests of ``benchmark/tests/test_loaded_cell.py``) as tier-1 tests;
see ``test_benchmark_harness.py``, which runs that file's other tests
and leaves these out by name. They find the cell's entries by name
(``benchmark/tests/cells.py``), so they read ``BENCHMARK.json`` whole.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.tests import test_loaded_cell as theirs  # noqa: E402

RUN = ["test_the_cell_and_its_configuration_are_what_the_issue_names",
       "test_every_metric_that_lists_the_cell_has_its_reader"]
globals().update({name: getattr(theirs, name) for name in RUN})


pytestmark = [pytest.mark.timeout(300),
              pytest.mark.usefixtures("benchmark_checkout")]
