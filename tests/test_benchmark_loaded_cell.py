"""What ``BENCHMARK.json`` lists for the cell ``backfill-1log-loaded``
(three tests of ``benchmark/tests/test_loaded_cell.py``) as tier-1
tests; see ``test_benchmark_harness.py``, which runs that file's other
tests and leaves these three out by name. They hold the cell's block to
the END of ``per_layer`` and the cells to six; ``conftest.py``'s
``listed_before_the_growing_cell`` hands them the list as it stood when
that was so (PR 47 owed this wrapper: PERF.md section 7).
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.tests import test_loaded_cell as theirs  # noqa: E402

RUN = ["test_the_cell_and_its_configuration_are_what_the_issue_names",
       "test_every_metric_that_lists_the_cell_has_its_reader",
       "test_the_older_cells_tests_read_the_list_without_the_cell"]
globals().update({name: getattr(theirs, name) for name in RUN})


def test_the_view_is_the_file_less_the_growing_cell():
    """What the three were handed: the whole file but for the one cell
    listed since, its configuration and the metrics that list it alone;
    nothing else moved."""
    import json

    with open(os.path.join(theirs.ROOT, "BENCHMARK.json")) as fh:
        whole = json.load(fh)
    seen = theirs.whole_bench()
    gone = "backfill-1log-growing"
    assert [w["name"] for w in whole["workloads"]] \
        == [w["name"] for w in seen["workloads"]] + [gone]
    assert [c for c in whole["configs"] if c not in seen["configs"]] \
        == [c for c in whole["configs"]
            if c["name"] == "icarus-dedup-growing-1chip"]
    taken = [m for m in whole["per_layer"] if m not in seen["per_layer"]]
    assert taken and all(m["workloads"] == [gone] for m in taken)
    assert seen["per_layer"] == whole["per_layer"][:len(seen["per_layer"])]
    assert {k: v for k, v in seen.items()
            if k not in ("workloads", "configs", "per_layer")} \
        == {k: v for k, v in whole.items()
            if k not in ("workloads", "configs", "per_layer")}


pytestmark = [pytest.mark.timeout(300),
              pytest.mark.usefixtures("benchmark_checkout")]
