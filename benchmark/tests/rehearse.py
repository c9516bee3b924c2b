#!/usr/bin/env python3
"""A whole run on whatever device JAX has, at a tiny table: the harness
without its look for a chip. For rehearsals on the CPU and for the tests
beside this file; ``run.py`` has no option that reaches it.

  JAX_PLATFORMS=cpu python3 benchmark/tests/rehearse.py [logs] [seed] [trace|notrace] [break] [key=value ...]

``traffic=<file>`` runs that traffic file as it stands (its sizes have
to be a rehearsal's own) in place of the cell's cut to size.
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


def run_once(config: dict, traffic: dict, *, seed: int, seconds: float,
             trace_on: bool, loadgen_cores: list[int],
             t_start: float) -> dict | None:
    """``run.py``'s run without its look for a chip; None (said on
    standard error) where the run cannot report."""
    import harness

    try:
        prep = harness.Prepared(config, traffic, seed=seed, seconds=seconds,
                                loadgen_cores=loadgen_cores)
    except harness.RunFailed as err:
        print(f"rehearse.py: {err} (jax loaded: {'jax' in sys.modules})",
              file=sys.stderr)
        return None
    try:
        import jax

        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
        return harness.run_cell(prep, trace_on=trace_on, t_start=t_start,
                                device=device)
    except harness.RunFailed as err:
        print(f"rehearse.py: {err}", file=sys.stderr)
        return None
    finally:
        prep.close()


def report(res: dict) -> None:
    print(json.dumps({"setup": res["setup"]}))
    print(json.dumps({"diagnosis": res["diagnosis"]}))
    for c in res["checks"]:
        print(json.dumps(c))
    print(json.dumps({"correct": res["correct"], "attempted": res["attempted"],
                      "failed": res["failed"], "values": res["values"],
                      "not_ok": [c["what"] for c in res["checks"]
                                 if not c["ok"]],
                      "device": res["device"],
                      "by_generator": res["by_generator"]}), flush=True)


def main(argv: list[str]) -> int:
    more = dict(a.split("=", 1) for a in argv if "=" in a)
    argv = [a for a in argv if "=" not in a]
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
    config, traffic = tiny(logs)
    if "traffic" in more:
        with open(more["traffic"]) as fh:
            traffic = json.load(fh)
    res = run_once(config, traffic, seed=seed, seconds=6.0, trace_on=trace_on,
                   loadgen_cores=loadgen_cores, t_start=T_START)
    if res is None:
        return 4
    if trace_on:
        import layers

        with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
            bench = json.load(fh)
        # No chip, so the device's metrics have nothing to read here.
        print(json.dumps(layers.read_all(bench, CELL, res, strict=False)))
    report(res)
    return 0 if res["correct"] else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
