"""The benchmark's rehearsals of a whole run on the CPU, sound and
broken (``benchmark/tests/test_benchmark.py``), as tier-1 tests; see
``test_benchmark_harness.py``.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.tests import test_benchmark as theirs  # noqa: E402

RUN = ["test_sound_run_is_correct",
       "test_traced_rehearsal_reads_the_host_layers",
       "test_lost_entry_is_not_correct",
       "test_deferred_checkpoint_is_not_correct",
       "test_a_cell_whose_files_are_wrong_fails_before_jax_loads"]
globals().update({name: getattr(theirs, name) for name in RUN})

pytestmark = [pytest.mark.timeout(300),
              pytest.mark.usefixtures("benchmark_checkout")]
