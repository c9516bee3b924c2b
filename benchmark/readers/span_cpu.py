"""What the spans of one name say beside their wall seconds, read from
the program's span ring like ``span_ring.py``: their thread's CPU time
(the event's ``tdur``), the time it was off a core under them (``dur -
tdur``: blocked, or waiting for the GIL), or one of their numeric
arguments (``gil_us``, ``wait_us``, ``requests``). ``span_ring.py`` and
``span_mean.py`` read ``dur``; this reader reads the rest. A span counts
where it ENDS inside the phase, whole: CPU time cannot be clipped.

params: ``terms``, a list of ``{"span": <name>, "take": <what>}`` whose
values are pooled (or ``span`` and ``take`` themselves, for one term);
``take`` is ``tdur``, ``offcore`` or ``arg:<argument>``, in microseconds
as the ring has them; ``value``: ``sum`` (the default), ``mean`` or
``p95`` (nearest rank) over the pooled values; ``per``: entry | batch |
none | ``arg:<argument>`` (the sum of that argument over the first
term's spans: requests a connection carried); ``phase`` (as
``span_ring.py``'s); ``scale``.

Not in this program (``layers.ABSENT``) as ``span_ring.py`` has it, by
the tracer and by each span's family, and also where a term takes
``tdur`` or ``offcore`` and no span of the whole ring carries a ``tdur``
(a program older than the field). Nothing to read (None: a listed
metric fails the run) where the ring dropped events inside the phase,
no span of a term ends in the phase, or one of them lacks what the term
takes (a span renamed, an argument gone, a ``tdur`` that some spans
have and this one does not).
"""

from __future__ import annotations

from layers import ABSENT
from readers import span_ring


def taken(ev: dict, take: str):
    """What ``take`` names of one span, in microseconds, or None."""
    if take == "tdur":
        return ev.get("tdur")
    if take == "offcore":
        return ev["dur"] - ev["tdur"] if "tdur" in ev else None
    if take.startswith("arg:"):
        value = ev.get("args", {}).get(take[4:])
        return value if isinstance(value, (int, float)) else None
    raise ValueError(f"unknown take {take!r}")


def nearest_rank(values: list[float], percent: int) -> float:
    """The smallest value with ``percent`` % of them at or under it."""
    ordered = sorted(values)
    return ordered[max(0, -(-percent * len(ordered) // 100) - 1)]


def read(params: dict, ctx: dict):
    ring = ctx["ring"] if "ring" in ctx else span_ring.live_ring()
    if ring is None:
        return ABSENT
    spans = [e for e in ring["events"] if e.get("ph") == "X"]
    if any("parent" not in e for e in spans):
        return ABSENT
    terms = params.get("terms") or [
        {"span": params["span"], "take": params["take"]}]
    if any(span_ring.family_absent(ring, spans, t["span"]) for t in terms):
        return ABSENT
    if any(not t["take"].startswith("arg:") for t in terms) \
            and not any("tdur" in e for e in spans):
        return ABSENT
    lo, hi = span_ring.phase_bounds(params.get("phase", "window"), ctx["out"])
    if hi <= lo or not span_ring.seen_whole(ring, spans, lo):
        return None
    t0 = ring["mono_t0"]
    ended = [e for e in spans if lo < t0 + (e["ts"] + e["dur"]) / 1e6 <= hi]
    pooled: list[float] = []
    for term in terms:
        values = [taken(e, term["take"]) for e in ended
                  if e["name"] == term["span"]]
        if not values or any(v is None for v in values):
            return None
        pooled += values
    value = params.get("value", "sum")
    if value == "sum":
        number = sum(pooled)
    elif value == "mean":
        number = sum(pooled) / len(pooled)
    elif value == "p95":
        number = nearest_rank(pooled, 95)
    else:
        raise ValueError(f"unknown value {value!r}")
    per = params.get("per", "none")
    if per.startswith("arg:"):
        carried = [taken(e, per) for e in ended
                   if e["name"] == terms[0]["span"]]
        if any(v is None for v in carried) or not sum(carried):
            return None
        base = sum(carried)
    else:
        base = {"entry": ctx["entries"], "batch": ctx["batches"],
                "none": 1}[per]
    return number / base * params.get("scale", 1.0)
