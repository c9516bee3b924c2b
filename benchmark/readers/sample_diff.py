"""Seconds the program spent under one of its own ``metrics.measure``
timers during the window (the samples that ended inside it, summed),
per entry, per batch or whole, scaled.

params: ``key`` (or ``key_prefix`` + ``key_suffix`` for per-log keys,
summed), ``per`` (entry | batch | none), ``scale``.
"""


def read(params: dict, ctx: dict):
    if "key" in params:
        mine = [v for k, v in ctx["samples"] if k == params["key"]]
    else:
        mine = [v for k, v in ctx["samples"]
                if k.startswith(params["key_prefix"])
                and k.endswith(params["key_suffix"])]
    if not mine:
        return None
    per = {"entry": ctx["entries"], "batch": ctx["batches"],
           "none": 1}[params.get("per", "none")]
    return sum(mine) / per * params.get("scale", 1.0)
