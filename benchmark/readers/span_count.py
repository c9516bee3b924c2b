"""Spans of one name and arguments that END inside a phase, read from the
program's span ring like ``span_ring.py``: how many there are (a round's
full checkpoints: one is right however many logs the round has), or the
mean of their seconds. ``span_ring.py`` sums a span's seconds; this
reader counts the spans, and knows one phase more: ``round`` =
``[t_open, t_durable]``, the whole round after the warm-up round, from
the log's opening to the checkpoint and every cursor on disk.

params: ``span`` (a name), ``args`` (an equality filter on the span's
arguments), ``phase`` (``round``, or one of ``span_ring.py``'s),
``value`` (``count``, the default, or ``mean_s``).

Not in this program (``layers.ABSENT``) as ``span_ring.py`` has it: by
the tracer, by the span's family. Nothing to read (None) where the ring
dropped events inside the phase, and for ``mean_s`` where no span of the
name lies in the phase. A count of none is a reading: 0.
"""

from __future__ import annotations

from layers import ABSENT
from readers import span_ring


def phase_bounds(phase: str, out: dict) -> tuple[float, float]:
    if phase == "round":
        return out["t_open"], out["t_durable"]
    return span_ring.phase_bounds(phase, out)


def read(params: dict, ctx: dict):
    ring = ctx["ring"] if "ring" in ctx else span_ring.live_ring()
    if ring is None:
        return ABSENT
    spans = [e for e in ring["events"] if e.get("ph") == "X"]
    if any("parent" not in e for e in spans) or span_ring.family_absent(
            ring, spans, params["span"]):
        return ABSENT
    lo, hi = phase_bounds(params.get("phase", "round"), ctx["out"])
    if hi <= lo or not span_ring.seen_whole(ring, spans, lo):
        return None
    t0 = ring["mono_t0"]
    want = params.get("args", {})
    mine = [e for e in spans if e["name"] == params["span"]
            and all(e.get("args", {}).get(k) == v for k, v in want.items())
            and lo <= t0 + (e["ts"] + e["dur"]) / 1e6 <= hi]
    if params.get("value", "count") == "count":
        return float(len(mine))
    if not mine:
        return None
    return sum(e["dur"] for e in mine) / 1e6 / len(mine)
