#!/usr/bin/env python3
"""A COMMITTED cell (its configuration and traffic files as
``BENCHMARK.json`` names them) cut to a rehearsal's size, on whatever
device JAX has: ``rehearse.py`` for a cell with generators beside the
log. ``run.py`` has no option that reaches it.

  JAX_PLATFORMS=cpu python3 benchmark/tests/rehearse_cell.py <cell> <seed> [trace] [<break>]

The cut: ``tableBits`` 18, ``batchSize`` 1,024, 64-entry pages, one
batch of warm-up, and the window's entries a second in the batch's
proportion, so that the window keeps its number of batches (64 for
``backfill-1log-query``) and a ``table_prefill`` block's ``slots_log2`` 18
with the table; a generator's ``min_age_s`` becomes
``TINY_MIN_AGE_S`` and its ``rate_per_s`` a quarter of the file's (the
CPU answers 96 a second alone, with a p99 of seconds; not under a test
suite's other workers). Every other parameter is the file's own. With
``trace`` it prints, before the result line, the cell's per-layer
metrics as ``layers.read_all`` reads them (the device's have nothing to
read without a chip).
"""

from __future__ import annotations

import json
import os
import sys
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, HERE)

TINY_BATCH = 1024
# A rehearsal's window is seconds long, so an age of 30 s would leave
# only the warm-up batch to ask after. Ten: the serials of the round's
# first seconds age in time, and a machine six times slower than this
# one alone (tier-1 under six workers) has still folded what it served
# ten seconds ago.
TINY_MIN_AGE_S = 10.0
TINY_RATE_SHARE = 0.25


def tiny(config: dict, traffic: dict) -> tuple[dict, dict]:
    batch = int(config["directives"]["batchSize"])
    config["directives"].update(tableBits=18, batchSize=TINY_BATCH)
    for g in traffic["generators"]:
        if g["kind"] == "log_replay":
            g.update(page=64, warmup_entries=TINY_BATCH,
                     window_entries_per_second=g["window_entries_per_second"]
                     * TINY_BATCH / batch)
            if "table_prefill" in g:  # the standing table follows tableBits
                g["table_prefill"] = dict(g["table_prefill"], slots_log2=18)
        else:
            if "min_age_s" in g:
                g["min_age_s"] = TINY_MIN_AGE_S
            if "rate_per_s" in g:
                g["rate_per_s"] *= TINY_RATE_SHARE
    return config, traffic


def main(argv: list[str]) -> int:
    if len(argv) < 2:
        sys.exit(__doc__)
    workload, seed = argv[0], int(argv[1])
    trace_on = "trace" in argv[2:]
    import run

    bench, _cell, config, traffic = run.load_cell(workload)
    loadgen_cores = run.split_cores()
    import breaks
    import rehearse

    for name in argv[2:]:
        if name != "trace":
            breaks.BREAKS[name]()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    config, traffic = tiny(config, traffic)
    res = rehearse.run_once(config, traffic, seed=seed,
                            seconds=float(bench["run_seconds"]),
                            trace_on=trace_on, loadgen_cores=loadgen_cores,
                            t_start=T_START)
    if res is None:
        return 4
    if trace_on:
        import layers

        print(json.dumps(layers.read_all(bench, workload, res, strict=False)))
    rehearse.report(res)
    return 0 if res["correct"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
