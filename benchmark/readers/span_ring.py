"""Seconds the program spent under one of its own spans, read from the
tracer's ring after the run: the run is in this process, the ring
outlives ``ct_fetch.main``, and its clock is the tracer's ``mono_t0``
plus an event's ``ts``, on ``time.monotonic()`` like the run's stamps.

params: ``span`` (a name) with an optional ``args`` equality filter, or
``uncovered`` (thread-name prefixes: the largest, over those threads,
of the phase's seconds that lie under no span at all); ``phase``
(``window`` = ``(t_first, t_folded]``, ``drain`` = ``[t_round_folded,
t_durable]``; spans are clipped to it); ``self`` (true: less the
children, by ``parent``); ``per`` (entry | batch | window_seconds |
span:<name> | none); ``scale``.

Not in this program (``layers.ABSENT``: the metric is left out) when
the program's tracer is off, records no ``parent`` or counts no drops (a
program older than these spans), or when the whole run's ring holds no
event of the span's family (``fetch.`` of ``fetch.enqueue``) and
dropped nothing. Nothing to read (None: a listed metric fails the run)
when the family is there and no span of the name lies in the phase (a
span renamed), or when the ring dropped events inside the phase.
"""

from __future__ import annotations

from layers import ABSENT


def live_ring() -> dict | None:
    """The program's ring as plain data, or None where its tracer is
    off or cannot say what it dropped."""
    from ct_mapreduce_tpu.telemetry import trace

    tracer = trace.get_tracer()
    if tracer is None or not hasattr(tracer, "dropped"):
        return None
    return {"events": tracer.events(), "mono_t0": tracer.mono_t0,
            "dropped": tracer.dropped()}


def phase_bounds(phase: str, out: dict) -> tuple[float, float]:
    if phase == "window":
        return out["t_first"], out["t_folded"]
    if phase == "drain":
        return out["t_round_folded"], out["t_durable"]
    raise ValueError(f"unknown phase {phase!r}")


def clipped(ev: dict, t0: float, lo: float, hi: float) -> float:
    """Seconds of the span inside ``[lo, hi]``."""
    start = t0 + ev["ts"] / 1e6
    return max(0.0, min(start + ev["dur"] / 1e6, hi) - max(start, lo))


def seen_whole(ring: dict, spans: list[dict], lo: float) -> bool:
    """The ring forgets its oldest events first: the phase was seen
    whole if nothing was dropped, or if what is left starts before it."""
    if not ring["dropped"]:
        return True
    t0 = ring["mono_t0"]
    return bool(spans) and min(
        t0 + (e["ts"] + e["dur"]) / 1e6 for e in spans) <= lo


def span_seconds(spans: list[dict], t0: float, lo: float, hi: float,
                 name: str, args: dict, self_time: bool) -> float | None:
    mine = [e for e in spans if e["name"] == name and all(
        e.get("args", {}).get(k) == v for k, v in args.items())
        and clipped(e, t0, lo, hi) > 0.0]
    if not mine:
        return None
    total = sum(clipped(e, t0, lo, hi) for e in mine)
    if self_time:
        ids = {e["id"] for e in mine}
        total -= sum(clipped(e, t0, lo, hi) for e in spans
                     if e["parent"] in ids)
    return total


def uncovered_seconds(ring: dict, spans: list[dict], lo: float, hi: float,
                      prefixes: list[str]) -> float | None:
    """Over the threads whose names start with one of ``prefixes``, the
    most seconds of ``[lo, hi]`` that lie under no span."""
    t0 = ring["mono_t0"]
    names = {e["tid"]: e["args"]["name"] for e in ring["events"]
             if e.get("ph") == "M" and e.get("name") == "thread_name"}
    worst = None
    for tid, tname in names.items():
        if not tname.startswith(tuple(prefixes)):
            continue
        ivals = sorted(
            (max(t0 + e["ts"] / 1e6, lo),
             min(t0 + (e["ts"] + e["dur"]) / 1e6, hi))
            for e in spans if e["tid"] == tid and not e["parent"])
        covered, edge = 0.0, lo
        for a, b in ivals:
            if b > max(a, edge):
                covered += b - max(a, edge)
                edge = b
        if ivals and covered > 0.0:
            bare = (hi - lo) - covered
            worst = bare if worst is None else max(worst, bare)
    return worst


def family_absent(ring: dict, spans: list[dict], name: str) -> bool:
    """No span of ``name``'s family in the whole run (and the ring
    forgot nothing, so the whole run is what it holds)."""
    family = name.split(".", 1)[0] + "."
    return not ring["dropped"] and not any(
        e["name"].startswith(family) for e in spans)


def read(params: dict, ctx: dict):
    ring = ctx["ring"] if "ring" in ctx else live_ring()
    if ring is None:
        return ABSENT
    spans = [e for e in ring["events"] if e.get("ph") == "X"]
    if any("parent" not in e for e in spans):
        return ABSENT
    named = [params["span"]] if "span" in params else []
    if params.get("per", "").startswith("span:"):
        named.append(params["per"][5:])
    if any(family_absent(ring, spans, name) for name in named):
        return ABSENT
    lo, hi = phase_bounds(params.get("phase", "window"), ctx["out"])
    if hi <= lo or not seen_whole(ring, spans, lo):
        return None
    t0 = ring["mono_t0"]
    if "uncovered" in params:
        seconds = uncovered_seconds(ring, spans, lo, hi, params["uncovered"])
    else:
        seconds = span_seconds(spans, t0, lo, hi, params["span"],
                               params.get("args", {}),
                               bool(params.get("self")))
    if seconds is None:
        return None
    per = params.get("per", "none")
    if per.startswith("span:"):
        base = span_seconds(spans, t0, lo, hi, per[5:], {}, False)
        if not base:
            return None
    else:
        base = {"entry": ctx["entries"], "batch": ctx["batches"],
                "window_seconds": hi - lo, "none": 1}[per]
    return seconds / base * params.get("scale", 1.0)
