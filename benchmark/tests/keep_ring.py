#!/usr/bin/env python3
"""A run of a cell that keeps the program's span ring, as the tracer
exports it, for ``tools/traceview.py`` (gunzip it first):

  python3 benchmark/tests/keep_ring.py <file.json.gz> --workload <cell> --seed <n> --seconds <s> --trace 1
"""

from __future__ import annotations

import gzip
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.dirname(BENCH))

import run  # noqa: E402


def keep_ring(path: str) -> None:
    from ct_mapreduce_tpu.telemetry import trace

    os.makedirs(os.path.dirname(path), exist_ok=True)
    plain = trace.export(path[:-3] if path.endswith(".gz") else path)
    if plain and plain != path:
        with open(plain, "rb") as src, gzip.open(path, "wb") as dst:
            shutil.copyfileobj(src, dst)
        os.unlink(plain)


def main(argv: list[str]) -> int:
    if len(argv) < 2:
        sys.exit(__doc__)
    rc = run.main(argv[1:])
    keep_ring(os.path.abspath(argv[0]))
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
