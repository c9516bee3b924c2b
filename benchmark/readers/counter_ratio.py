"""One of the program's counters over others, each summed over a phase
like ``counter_sum.py``: device calls a batch of queries, the share of
probed lanes that were padding. ``counter_per.py`` divides by what the
harness counts (batches, entries); this reader divides by what the
program counts.

params: ``key`` (the counter's dotted name), ``over`` (the counters
whose sums add up to the divisor), ``phase`` (as ``span_count.py``'s;
``window`` is the default), ``scale``.

Not in this program (``layers.ABSENT``) as ``counter_sum.py`` has it,
for ``key`` and for every counter of ``over``. Nothing to read (None)
where the divisor is 0: the program counts and the phase held nothing.
"""

from __future__ import annotations

from layers import ABSENT
from readers import counter_sum


def read(params: dict, ctx: dict):
    phase = params.get("phase", "window")
    sums = [counter_sum.read({"key": key, "phase": phase}, ctx)
            for key in (params["key"], *params["over"])]
    if any(s is ABSENT for s in sums):
        return ABSENT
    below = sum(sums[1:])
    if below <= 0.0:
        return None
    return sums[0] / below * params.get("scale", 1.0)
