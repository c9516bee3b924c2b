#!/usr/bin/env python3
"""A run with the per-layer metrics of ``pending_per_layer.json`` read
beside those ``BENCHMARK.json`` lists: the way to see them until a
benchmark PR lists them (that file says what stands in the way).

  python3 benchmark/tests/pending.py run [--ring <file.json.gz>] --workload <cell> --seed <n> --seconds <s> --trace 1
  JAX_PLATFORMS=cpu python3 benchmark/tests/pending.py rehearse [logs] [seed] trace

``--ring`` keeps the program's span ring of the run, as the tracer
exports it, for ``tools/traceview.py`` (gunzip it first).
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, HERE)

import run  # noqa: E402


def install() -> None:
    """Make ``layers.read_all`` read the pending entries too. Called
    once the process's cores are split (``layers`` loads numpy)."""
    import layers

    with open(os.path.join(BENCH, "pending_per_layer.json")) as fh:
        pending = json.load(fh)["per_layer"]
    read_all = layers.read_all

    def with_pending(bench, workload, res, strict=True):
        listed = {m["name"] for m in bench["per_layer"]}
        more = [m for m in pending if m["name"] not in listed]
        return read_all(dict(bench, per_layer=bench["per_layer"] + more),
                        workload, res, strict)

    layers.read_all = with_pending


def keep_ring(path: str) -> None:
    from ct_mapreduce_tpu.telemetry import trace

    os.makedirs(os.path.dirname(path), exist_ok=True)
    plain = trace.export(path[:-3] if path.endswith(".gz") else path)
    if plain and plain != path:
        with open(plain, "rb") as src, gzip.open(path, "wb") as dst:
            shutil.copyfileobj(src, dst)
        os.unlink(plain)


def main(argv: list[str]) -> int:
    if argv[0] == "run":
        ring = None
        if argv[1] == "--ring":
            ring, argv = os.path.abspath(argv[2]), argv[:1] + argv[3:]
        rc = run.main(argv[1:], before=install)
        if ring:
            keep_ring(ring)
        return rc
    if argv[0] != "rehearse":
        sys.exit(__doc__)
    import rehearse

    split_cores = run.split_cores

    def split_then_install():
        cores = split_cores()
        install()
        return cores

    run.split_cores = split_then_install
    return rehearse.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
