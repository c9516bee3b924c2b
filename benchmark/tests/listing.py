"""``BENCHMARK.json`` as the per-cell tests beside this file read it.

Those tests hold a cell's metrics to places in ``per_layer`` ("the
cell's block ends the list", "the seven stand at the end", "every
metric that lists several cells lists these three"), and so do their
tier-1 wrappers under ``tests/``, which a PR that may touch only the
benchmark cannot edit (ROADMAP R0k). A cell that is listed after they
were written would fail them all by being there. So they read the file
through :func:`before`, which takes the later cells out again: their
entries of ``workloads``, the configurations only they use, their names
in every metric's ``workloads`` and the metrics that then list no cell.
What a later cell lists has tests of its own (``test_loaded_cell.py``).
"""

from __future__ import annotations

import json
import os

# In the order they were listed; each after every test that reads
# through this file was written.
LATER_CELLS = ("backfill-1log-loaded",)


def before(bench: dict, later: tuple[str, ...] = LATER_CELLS) -> dict:
    workloads = [w for w in bench["workloads"] if w["name"] not in later]
    used = {w["config"] for w in workloads}
    per_layer = []
    for metric in bench["per_layer"]:
        if "workloads" in metric:
            cells = [c for c in metric["workloads"] if c not in later]
            if not cells:
                continue
            metric = dict(metric, workloads=cells)
        per_layer.append(metric)
    return dict(bench, workloads=workloads, per_layer=per_layer,
                configs=[c for c in bench["configs"] if c["name"] in used])


def bench_json(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return before(json.load(fh))
