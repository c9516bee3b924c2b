"""How far apart two of the program's gauges stand, over a third: the
fullest shard's fill less the emptiest's over the mean. The harness's
emitter keeps samples and counters with their instants and lets gauges
pass, so this reads the program's own metrics sink after the run (the
run is in this process, and the sink outlives ``ct_fetch.main`` as the
span ring does): what each gauge held last. For gauges set at every
full save that is the round's save: nothing is folded after
``t_durable``, and a later save of the same table sets the same numbers.

params: ``hi``, ``lo``, ``over`` (gauges' dotted names), ``scale``.

Not in this program (``layers.ABSENT``) where the sink holds none of
the three: a program that does not set them, or a deployment that has
nothing to set them for (one chip has no shards). Nothing to read
(None) where it holds some and not all, or ``over`` reads 0.
"""

from __future__ import annotations

from layers import ABSENT


def live_gauges() -> dict:
    from ct_mapreduce_tpu.telemetry import metrics

    return metrics.get_sink().snapshot()["gauges"]


def read(params: dict, ctx: dict):
    gauges = ctx["gauges"] if "gauges" in ctx else live_gauges()
    found = [gauges.get(params[k]) for k in ("hi", "lo", "over")]
    if all(v is None for v in found):
        return ABSENT
    if any(v is None for v in found) or not found[2]:
        return None
    hi, lo, over = found
    return (hi - lo) / over * params.get("scale", 1.0)
