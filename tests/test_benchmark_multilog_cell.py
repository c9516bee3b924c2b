"""The tests of the cell ``backfill-3log``
(``benchmark/tests/test_multilog_cell.py``) as tier-1 tests; see
``test_benchmark_harness.py``. Two of them are whole rehearsals of the
committed cell at a tiny table.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.tests import test_multilog_cell as theirs  # noqa: E402,F401
from benchmark.tests.test_multilog_cell import *  # noqa: E402,F401,F403

pytestmark = [pytest.mark.timeout(300),
              pytest.mark.usefixtures("benchmark_checkout")]
