"""A mean over the spans of one name that END inside the phase, read
from the program's span ring like ``span_ring.py``: of their seconds, or
of one of their numeric arguments (what a capture moved over the host
link). ``span_ring.py`` divides a span's seconds by entries, batches or
seconds; this reader divides by how many spans there were.

params: ``span`` (a name), ``arg`` (an argument to average; without it
the span's own seconds), ``phase`` (as ``span_ring.py``'s), ``scale``.

Not in this program (``layers.ABSENT``) and nothing to read (None) as
``span_ring.py`` has them: by the tracer, by the span's family, by the
drops. A span without the argument is a span renamed: None.
"""

from __future__ import annotations

from layers import ABSENT
from readers import span_ring


def read(params: dict, ctx: dict):
    ring = ctx["ring"] if "ring" in ctx else span_ring.live_ring()
    if ring is None:
        return ABSENT
    spans = [e for e in ring["events"] if e.get("ph") == "X"]
    if any("parent" not in e for e in spans) or span_ring.family_absent(
            ring, spans, params["span"]):
        return ABSENT
    lo, hi = span_ring.phase_bounds(params.get("phase", "window"), ctx["out"])
    if hi <= lo or not span_ring.seen_whole(ring, spans, lo):
        return None
    t0 = ring["mono_t0"]
    mine = [e for e in spans if e["name"] == params["span"]
            and lo < t0 + (e["ts"] + e["dur"]) / 1e6 <= hi]
    if not mine:
        return None
    if "arg" in params:
        values = [e.get("args", {}).get(params["arg"]) for e in mine]
        if any(not isinstance(v, (int, float)) for v in values):
            return None
    else:
        values = [e["dur"] / 1e6 for e in mine]
    return sum(values) / len(values) * params.get("scale", 1.0)
