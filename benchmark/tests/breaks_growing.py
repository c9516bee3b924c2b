#!/usr/bin/env python3
"""The control of the configuration ``icarus-dedup-growing-1chip``'s
guarantee **growth**, beside ``breaks.py`` (which holds the older
guarantees' and is run the same way): a run of the cell with one thing
broken underneath, whose last line must read ``"correct": false``.

  python3 benchmark/tests/breaks_growing.py growth_disabled --workload backfill-1log-growing --seed <n> --seconds <s> --trace 0
  JAX_PLATFORMS=cpu python3 benchmark/tests/breaks_growing.py rehearse backfill-1log-growing <seed> [trace] [<break>]

The first is ``control.py``'s run on the chip at the cell's own size,
the second ``rehearse_cell.py``'s at a rehearsal's; both know this
file's break beside ``breaks.py``'s.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

import breaks  # noqa: E402


def growth_disabled() -> None:
    """Growth -> the table never grows: the same run with ``tableGrowAt
    = 0`` in the ini, as for a tailer whose growth policy is off or
    broken. The table passes its threshold and stays as it is (a lane
    whose probe chain overflows takes the exact host lane), so counts
    stay exact and every comparison of the log holds; the table's
    watcher reads no growth and the old slots."""
    import harness

    real = harness.write_ini

    def write_ini(config, *args, **kwargs):
        config = dict(config, directives=dict(config["directives"],
                                              tableGrowAt=0))
        return real(config, *args, **kwargs)

    harness.write_ini = write_ini


breaks.BREAKS["growth_disabled"] = growth_disabled


def main(argv: list[str]) -> int:
    if argv[0] == "rehearse":
        import rehearse_cell

        return rehearse_cell.main(argv[1:])
    import control

    return control.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
