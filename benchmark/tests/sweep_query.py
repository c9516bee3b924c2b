#!/usr/bin/env python3
"""The query plane under the backfill stream, at the cell's own size, before
any cell lists it: ``icarus-dedup-1chip``'s directives plus a free
``queryPort`` (``serveReplicas``, ``serveDevice``, ``serveCacheSize`` at
the program's defaults) under ``tests/traffic/backfill-1log-query.json``
with the ``query_poisson`` generator at a rate given here. ``run.py`` has
no option that reaches it (the ``rehearse.py`` pattern).

  python3 benchmark/tests/sweep_query.py step <rate> <seed> [spans] [tiny] [<break>]
  python3 benchmark/tests/sweep_query.py sweep <seed> <first rate> [<last rate>]
  python3 benchmark/tests/sweep_query.py repeat <rate> <seed> [<seed> ...]

``step`` is one run on this machine's device and prints one line;
``spans`` turns the program's span tracer on (not the profiler) and adds
what the ``serve.*`` spans of the window read; ``tiny`` cuts the table
and the stream to a rehearsal's on the CPU. ``sweep`` doubles the rate
from the first, a process a step, until a step has failed requests (or
past the last rate); ``repeat`` runs one rate on several seeds. Both
append every line to ``chiprun_out/sweep_query.jsonl``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

SECONDS = 35.0  # BENCHMARK.json's run_seconds: the window a cell would get
KIND = "query_poisson"


def cell(rate: float, tiny: bool = False) -> tuple[dict, dict]:
    with open(os.path.join(BENCH, "configs", "icarus-dedup-1chip.json")) as fh:
        config = json.load(fh)
    config["ports"] = ["queryPort"]
    with open(os.path.join(HERE, "traffic", "backfill-1log-query.json")) as fh:
        traffic = json.load(fh)
    for g in traffic["generators"]:
        if g["kind"] == KIND:
            g["rate_per_s"] = rate
            if tiny:  # the snapshots are a second old at most: age past it
                g["min_age_s"] = 2.5
        elif tiny:  # 96 batches of 1,024: some 5 s on the CPU
            g.update(page=64, warmup_entries=1024,
                     window_entries_per_second=96 * 1024 / SECONDS)
    if tiny:
        config["directives"].update(tableBits=18, batchSize=1024)
    return config, traffic


def span_table(lo: float, hi: float, prefix: str) -> dict:
    """Per span name under ``prefix``, over the spans that end inside
    ``(lo, hi]``: how many, their seconds, and the median, the 95th
    percentile and the longest in milliseconds."""
    import fixture as fx
    from readers import span_ring

    ring = span_ring.live_ring()
    by_name: dict[str, list[float]] = {}
    for e in ring["events"]:
        if e.get("ph") == "X" and e["name"].startswith(prefix) and \
                lo < ring["mono_t0"] + (e["ts"] + e["dur"]) / 1e6 <= hi:
            by_name.setdefault(e["name"], []).append(e["dur"] / 1e3)
    return {name: {"n": len(ms), "seconds": sum(ms) / 1e3,
                   "p50_ms": fx.quantile(ms, 0.5),
                   "p95_ms": fx.quantile(ms, 0.95), "max_ms": max(ms)}
            for name, ms in sorted(by_name.items())} | {
                "ring_dropped": ring["dropped"]}


def step(argv: list[str]) -> int:
    rate, seed = float(argv[0]), int(argv[1])
    spans, tiny = "spans" in argv[2:], "tiny" in argv[2:]
    import run

    run.cache_env()
    loadgen_cores = run.split_cores()
    import breaks
    import rehearse

    for name in argv[2:]:
        if name not in ("spans", "tiny"):
            breaks.BREAKS[name]()
    if spans:
        from ct_mapreduce_tpu.telemetry import trace

        trace.enable(ring_size=1 << 20, jax_annotations=False)
    config, traffic = cell(rate, tiny)
    if tiny:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    res = rehearse.run_once(config, traffic, seed=seed, seconds=SECONDS,
                            trace_on=False, loadgen_cores=loadgen_cores,
                            t_start=T_START)
    if res is None:
        return 4
    print(json.dumps({"diagnosis": res["diagnosis"]}), file=sys.stderr)
    for c in res["checks"]:
        print(json.dumps(c), file=sys.stderr)
    out, values = res["out"], res["values"]
    peak = res["device"]["memory_peak_bytes"]
    line = {"rate_per_s": rate, "seed": seed, "correct": res["correct"],
            "not_ok": [c["what"] for c in res["checks"] if not c["ok"]],
            "by_generator": res["by_generator"], "values": values,
            "notes": res["diagnosis"][KIND],
            "log_server_ms": res["diagnosis"]["page_server_ms"],
            "window_s": out["t_folded"] - out["t_first"],
            "peak_hbm_gb": None if peak is None else peak / 1e9,
            "device": res["device"]["kind"]}
    if spans:
        line["serve_spans"] = span_table(out["t_first"], out["t_folded"],
                                         "serve.")
    print(json.dumps(line), flush=True)
    return 0


def child(*args: str) -> dict | None:
    """One step in a process of its own (the chip is one process's at a
    time), its line kept and returned."""
    res = subprocess.run([sys.executable, os.path.abspath(__file__), "step",
                          *args], capture_output=True, text=True, cwd=ROOT)
    lines = res.stdout.strip().splitlines()
    if res.returncode != 0 or not lines:
        print(f"step {args}: rc {res.returncode}\n{res.stderr[-1500:]}",
              flush=True)
        return None
    line = json.loads(lines[-1])
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "sweep_query.jsonl"),
              "a") as fh:
        fh.write(lines[-1] + "\n")
    v = line["values"]
    print(json.dumps({
        "rate": line["rate_per_s"], "seed": line["seed"],
        "correct": line["correct"], "sent": v["query_sent"],
        "failed": v["query_failed"], "p50": round(v["query_p50_ms"], 1),
        "p95": round(v["query_p95_ms"], 1), "p99": round(v["query_p99_ms"], 1),
        "ingest": round(v["ingest_entries_per_s"]),
        "setup_s": round(v["setup_s"], 1),
        "window_s": round(line["window_s"], 1), "hbm": line["peak_hbm_gb"],
        "sent_late": v["query_sent_late"],
        "log_server_max_ms": round(line["log_server_ms"]["100"], 1),
        "not_ok": line["not_ok"]}), flush=True)
    return line


def main(argv: list[str]) -> int:
    if len(argv) >= 3 and argv[0] == "step":
        return step(argv[1:])
    if len(argv) >= 3 and argv[0] == "sweep":
        rate, last = float(argv[2]), float(argv[3]) if len(argv) > 3 else 4096
        while rate <= last:
            line = child(str(rate), argv[1])
            if line is None or line["values"]["query_failed"]:
                break
            rate *= 2
        return 0
    if len(argv) >= 3 and argv[0] == "repeat":
        for seed in argv[2:]:
            child(argv[1], seed)
        return 0
    sys.exit(__doc__)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
