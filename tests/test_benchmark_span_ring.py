"""The benchmark's span-ring readers and their rehearsal
(``benchmark/tests/test_span_ring.py``) as tier-1 tests; see
``test_benchmark_harness.py``.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.tests import test_span_ring as theirs  # noqa: E402,F401
from benchmark.tests.test_span_ring import *  # noqa: E402,F401,F403

pytestmark = [pytest.mark.timeout(300),
              pytest.mark.usefixtures("benchmark_checkout")]
