"""Device seconds from the profile over the window: of the programs
(``XLA Modules``) or ops (``XLA Ops``) whose names contain ``match``,
whole or per lane they processed (calls inside the window times
``lanes_per_call``: a step dispatched before the window opened can end
inside it); or the device's idle share of the window.

params: ``what`` (modules | ops | idle_pct), ``match``, ``lanes_per_call``
(a number, or ``batchSize`` for the configuration's), ``scale``.
"""


def read(params: dict, ctx: dict):
    trace = ctx["trace"]
    if params["what"] == "idle_pct":
        if trace["busy_s"] <= 0.0:
            return None
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    table = trace[params["what"]]
    calls = trace[params["what"][:-1] + "_calls"]
    seconds = sum(v for k, v in table.items() if params["match"] in k)
    n = sum(v for k, v in calls.items() if params["match"] in k)
    if seconds <= 0.0:
        return None
    lanes = params.get("lanes_per_call")
    if lanes == "batchSize":
        lanes = int(ctx["config"]["directives"]["batchSize"])
    per = n * lanes if lanes else 1
    return seconds / per * params.get("scale", 1.0)
