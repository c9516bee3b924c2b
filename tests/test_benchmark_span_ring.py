"""The benchmark's span-ring readers and their rehearsal
(``benchmark/tests/test_span_ring.py``) as tier-1 tests; see
``test_benchmark_harness.py``.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.tests import test_span_ring as theirs  # noqa: E402
from benchmark.tests.test_span_ring import *  # noqa: E402,F401,F403



def test_ring_metrics_are_listed_after_the_thirteen():  # noqa: F811
    """Theirs, for a ``BENCHMARK.json`` that has grown since (theirs
    holds the list to 22 entries, and a PR that adds a cell may edit no
    file under ``benchmark/``: ROADMAP R0): the nine still follow the
    thirteen, and whatever a later cell lists comes after them."""
    with open(os.path.join(theirs.ROOT, "BENCHMARK.json")) as fh:
        listed = json.load(fh)["per_layer"]
    assert [m["name"] for m in listed[13:22]] == list(theirs.RING_METRICS)
    for m in theirs.ring_metrics():
        assert m["source"] == "program_span"
        assert m["workloads"] == ["backfill-1log"]
        with open(os.path.join(theirs.BENCH, "layers",
                               m["name"] + ".json")) as fh:
            assert json.load(fh)["reader"] == "span_ring"


pytestmark = [pytest.mark.timeout(300),
              pytest.mark.usefixtures("benchmark_checkout")]
