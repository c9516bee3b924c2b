#!/usr/bin/env python3
"""A whole run on whatever device JAX has, at a tiny table: the harness
without its look for a chip. For rehearsals on the CPU and for the tests
beside this file; ``run.py`` has no option that reaches it.

  JAX_PLATFORMS=cpu python3 benchmark/tests/rehearse.py [logs] [seed] [trace|notrace] [break]
"""

from __future__ import annotations

import json
import os
import sys
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)


CELL = "backfill-1log"


def tiny(logs: int) -> tuple[dict, dict]:
    with open(os.path.join(BENCH, "configs", "icarus-dedup-1chip.json")) as fh:
        config = json.load(fh)
    config["directives"].update(tableBits=16, batchSize=1024)
    with open(os.path.join(BENCH, "traffic", CELL + ".json")) as fh:
        traffic = json.load(fh)
    for g in traffic["generators"]:
        g.update(logs=logs, page=64, warmup_entries=1024,
                 window_entries_per_second=1024)
    return config, traffic


def main(argv: list[str]) -> int:
    logs = int(argv[0]) if argv else 1
    seed = int(argv[1]) if len(argv) > 1 else 2468013579
    trace_on = len(argv) > 2 and argv[2] == "trace"
    import run

    loadgen_cores = run.split_cores()
    if len(argv) > 3:  # break a guarantee underneath (breaks.py)
        sys.path.insert(0, HERE)
        import breaks

        breaks.BREAKS[argv[3]]()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import harness

    config, traffic = tiny(logs)
    prep = harness.Prepared(config, traffic, seed=seed, seconds=6.0,
                            loadgen_cores=loadgen_cores)
    try:
        import jax

        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
        res = harness.run_cell(prep, trace_on=trace_on, t_start=T_START,
                               device=device)
    finally:
        prep.close()
    if trace_on:
        import layers

        with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
            bench = json.load(fh)
        # No chip, so the device's metrics have nothing to read here.
        print(json.dumps(layers.read_all(bench, CELL, res, strict=False)))
    print(json.dumps({"setup": res["setup"]}))
    print(json.dumps({"diagnosis": res["diagnosis"]}))
    for c in res["checks"]:
        print(json.dumps(c))
    print(json.dumps({"correct": res["correct"], "attempted": res["attempted"],
                      "failed": res["failed"], "values": res["values"],
                      "not_ok": [c["what"] for c in res["checks"]
                                 if not c["ok"]],
                      "device": res["device"]}))
    return 0 if res["correct"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
