#!/usr/bin/env python3
"""The control: a run of a cell, on the chip and at the cell's own size,
with one guarantee of its configuration broken underneath the timed
path (see breaks.py). Its last line must read ``"correct": false``.

  python3 benchmark/tests/control.py <break> --workload <cell> --seed <n> --seconds <s> --trace 0
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

import run  # noqa: E402


def main(argv: list[str]) -> int:
    import breaks

    return run.main(argv[1:], before=breaks.BREAKS[argv[0]])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
