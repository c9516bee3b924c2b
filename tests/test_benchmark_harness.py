"""The benchmark's own tests (``benchmark/tests/test_benchmark.py``) as
tier-1 tests: the driver runs ``tests/``, and the yardstick every PR is
judged by should be able to refuse one. This file has the fixture's
arithmetic, the readers on a recorded trace, ``BENCHMARK.json``'s
contract and ``run.py``'s refusal without a chip, and whatever test is
added there later; the rehearsals of a whole run are in
``test_benchmark_rehearsals.py`` and ``test_benchmark_query.py``, so
that ``--dist loadfile`` can give each a worker of its own, and what
``BENCHMARK.json`` lists for ``backfill-1log-loaded`` is in
``test_benchmark_loaded_cell.py``.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.tests import test_benchmark as theirs  # noqa: E402
from benchmark.tests.test_benchmark import *  # noqa: E402,F401,F403
from tests import (  # noqa: E402
    test_benchmark_loaded_cell,
    test_benchmark_query,
    test_benchmark_rehearsals,
)

for _name in (test_benchmark_query.RUN + test_benchmark_query.LEFT_OUT
              + test_benchmark_rehearsals.RUN
              + test_benchmark_loaded_cell.RUN):
    del globals()[_name]  # they run there, or are left out there by name

# The slowest (run.py's refusal) is 61 s alone on the CPU.
pytestmark = [pytest.mark.timeout(300),
              pytest.mark.usefixtures("benchmark_checkout")]
