"""One of the program's counters summed over a phase, from the
increments the harness's emitter on the program's metrics sink saw
(``out["counters"]``: every increment since the log opened, with its
instant).

params: ``key`` (the counter's dotted name), ``phase`` (as
``span_count.py``'s: ``round`` = ``[t_open, t_durable]``, the default).

Not in this program (``layers.ABSENT``) where the whole round holds no
increment of the counter, not even one of 0: a program that counts what
``key`` counts says so on every occasion (``ingest.partial_batches``
adds 0 for a dispatch of a whole batch), so "none was short" reads 0.0
and "this program does not count them" leaves the metric out.
"""

from __future__ import annotations

from layers import ABSENT
from readers import span_count


def read(params: dict, ctx: dict):
    out = ctx["out"]
    mine = [(t, v) for t, k, v in out["counters"] if k == params["key"]]
    if not mine:
        return ABSENT
    lo, hi = span_count.phase_bounds(params.get("phase", "round"), out)
    return float(sum(v for t, v in mine if lo <= t <= hi))
