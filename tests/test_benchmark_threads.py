"""The benchmark's reader of a span's CPU time and arguments and the
seven metrics of the layer "host threads (the GIL)"
(``benchmark/tests/test_threads.py``) as tier-1 tests; see
``test_benchmark_harness.py``.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.tests import test_threads as theirs  # noqa: E402,F401
from benchmark.tests.test_threads import *  # noqa: E402,F401,F403

pytestmark = [pytest.mark.timeout(300),
              pytest.mark.usefixtures("benchmark_checkout")]
