#!/usr/bin/env python3
"""The benchmark's one command:

  python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration under
``benchmark/configs/`` and its traffic under ``benchmark/traffic/``;
runs it once on the machine it is started on (see ``harness.py``); and
prints, as its last line, one JSON object: ``correct``, ``attempted``,
``failed`` (the sums over the traffic's generators, each apart under
``by_generator``), ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``
and, last, ``compared``: every number compared beside its limit. Lines
before it itemise the set-up and the run's phases; the numbers compared
are also the last lines on standard error.

Without the cell's TPU devices it exits 3 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402


def split_cores() -> list[int]:
    """Keep the load generator and the program on cores of their own:
    the last two of this process's cores are set aside for the log
    server, and this process keeps the others (and so does every thread
    it starts later: call this before anything that starts one)."""
    try:
        cores = sorted(os.sched_getaffinity(0))
        if len(cores) < 4:
            return []
        os.sched_setaffinity(0, cores[:-2])
    except (AttributeError, OSError):
        return []
    return cores[-2:]


HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)


def load_cell(workload: str) -> tuple[dict, dict, dict, dict]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        sys.exit(f"run.py: no workload {workload!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as fh:
        config = json.load(fh)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as fh:
        traffic = json.load(fh)
    return bench, cell, config, traffic


def cache_env() -> None:
    """JAX's persistent compile cache: one fixed directory inside the
    checkout that only the benchmark writes, every program kept whatever
    it cost to compile, nothing evicted (a machine that sets a maximum
    size makes JAX keep access-time files, and one entry written without
    them then fails every later write). Set before JAX loads; the
    program's own helper takes the directory it is given."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        ROOT, ".bench_cache", "jax")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs to /tmp


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def main(argv: list[str] | None = None, before=None) -> int:
    """``before``, if given, is called once the environment is set and
    before the program is loaded (the control's way in: tests/control.py)."""
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    bench, cell, config, traffic = load_cell(args.workload)

    cache_env()
    loadgen_cores = split_cores()
    if before is not None:
        before()
    try:
        import ct_mapreduce_tpu
    except ImportError as err:
        sys.exit(f"run.py: the program is not in this checkout: {err}")
    if not os.path.abspath(ct_mapreduce_tpu.__file__).startswith(ROOT + os.sep):
        sys.exit("run.py: the program must be this checkout's own, not "
                 f"{ct_mapreduce_tpu.__file__}")
    import harness

    try:
        # The log server first: it builds the run's pages while JAX loads.
        prep = harness.Prepared(config, traffic, seed=args.seed,
                                seconds=args.seconds,
                                loadgen_cores=loadgen_cores)
    except harness.RunFailed as err:
        print(f"run.py: {err}", file=sys.stderr)
        return 4
    try:
        import jax

        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
        if device["platform"] != "tpu" or device["count"] != cell["chips"]:
            print(f"run.py: {args.workload} needs {cell['chips']} TPU "
                  f"device(s); JAX found {device}", file=sys.stderr)
            return 3
        res = harness.run_cell(prep, trace_on=bool(args.trace),
                               t_start=T_START, device=device)
    except harness.RunFailed as err:
        print(f"run.py: {err}", file=sys.stderr)
        return 4
    finally:
        prep.close()

    print(json.dumps({"setup_s_itemised": res["setup"]}), flush=True)
    print(json.dumps({"diagnosis": res["diagnosis"]}), flush=True)
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "by_generator": res["by_generator"],
            "device": res["device"]}
    if args.trace:
        import layers

        try:
            metrics, device_times, breakdown = layers.read_all(
                bench, args.workload, res)
        except harness.RunFailed as err:
            print(f"run.py: {err}", file=sys.stderr)
            return 4
        line["metrics"] = metrics
        line["device"].update(device_times)
        line["breakdown"] = breakdown
    else:
        line["metrics"] = {
            m["name"]: {"value": res["values"][m["name"]], "unit": m["unit"]}
            for m in bench["end_to_end"] if reports(m, args.workload)}
    line["compared"] = res["checks"]
    for check in res["checks"]:
        print(json.dumps({"compared": check}), file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
