"""One of the program's counters summed over a phase like
``counter_sum.py`` and divided: by the window's batches or entries,
scaled (bytes a batch's rows crossed the host-device boundary, in MB a
batch). ``counter_sum.py`` gives the sum alone.

params: ``key`` (the counter's dotted name), ``phase`` (as
``span_count.py``'s; ``window``, the default, is what ``per`` counts),
``per`` (batch | entry | none), ``scale``.

Not in this program (``layers.ABSENT``) as ``counter_sum.py`` has it:
the whole round holds no increment of the counter, not even one of 0.
"""

from __future__ import annotations

from layers import ABSENT
from readers import counter_sum


def read(params: dict, ctx: dict):
    total = counter_sum.read(
        {"key": params["key"], "phase": params.get("phase", "window")}, ctx)
    if total is ABSENT:
        return ABSENT
    per = {"batch": ctx["batches"], "entry": ctx["entries"],
           "none": 1}[params.get("per", "none")]
    return total / per * params.get("scale", 1.0)
