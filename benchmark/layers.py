"""The traced run's per-layer metrics. Each metric of ``BENCHMARK.json``'s
``per_layer`` has a file ``layers/<name>.json`` naming its reader (a
module under ``readers/``) and the reader's parameters; a later PR adds
a metric by adding such a file (and a reader, if none fits).

A reader has two ways to give no number. ``None``: the program has the
feature and the run has nothing to read of it (a span or a kernel
renamed, a phase with no event in it, a ring that dropped events inside
the phase): a metric that ``BENCHMARK.json`` lists for the cell fails
the run by name. :data:`ABSENT`: the program under test has no such
feature at all (an older program whose tracer records no ``parent``;
no event of the span's family anywhere in the run; a directive the
metric hangs on that the configuration does not have): the metric is
left out of that run's line and named on a printed ``{"absent": [...]}``
line, so that a PR can list a metric whose span its parent lacks.
"""

from __future__ import annotations

import importlib
import json
import os

import tracing
from harness import RunFailed

HERE = os.path.dirname(os.path.abspath(__file__))


class _Absent:
    def __repr__(self) -> str:
        return "ABSENT"


ABSENT = _Absent()  # a reader's answer: "not in this program"


def context(res: dict) -> dict:
    """What readers read: the run's stamps and counts, the program's
    metric samples at the window's two ends, and the reduced trace."""
    out = res["out"]
    tracer = out["tracer"]
    xp = tracing.load_xplane(tracer.xplane())
    lo, hi = out["t_first"], out["t_folded"]
    spans, shift = tracing.host_intervals(xp, tracer.anchor, out["pages"])
    reduced = tracing.reduce_trace(xp, lo + shift, hi + shift, spans)
    inside = lambda rows: [(k, v) for t, k, v in rows if lo < t <= hi]  # noqa: E731
    return {
        "entries": res["spec"].window_entries,
        "batches": res["spec"].window_entries
        // int(res["config"]["directives"]["batchSize"]),
        "seconds": hi - lo,
        "values": res["values"], "out": out,
        "samples": inside(out["samples"]), "counters": inside(out["counters"]),
        "host_spans": {k: [iv for iv in v if iv[1] >= lo + shift
                           and iv[0] <= hi + shift]
                       for k, v in spans.items()},
        "trace": reduced, "xplane_lines": xp["lines"],
        "headroom": res["headroom"], "compiles": res["compiles"],
        "device": res["device"], "config": res["config"],
    }


def read_metrics(entries: list[dict], workload: str, ctx: dict,
                 strict: bool = True) -> tuple[dict, list[str]]:
    """The cell's metrics of ``entries`` (``BENCHMARK.json``'s
    ``per_layer``) read from ``ctx``, and the names of those the
    program under test does not have."""
    metrics, absent = {}, []
    for entry in entries:
        if "workloads" in entry and workload not in entry["workloads"]:
            continue
        with open(os.path.join(HERE, "layers", entry["name"] + ".json")) as fh:
            spec = json.load(fh)
        reader = importlib.import_module("readers." + spec["reader"])
        value = reader.read(spec.get("params", {}), ctx)
        if value is ABSENT:
            absent.append(entry["name"])
        elif value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
        elif strict:
            raise RunFailed(f"per-layer metric {entry['name']} found nothing "
                            f"to read in {workload}")
    return metrics, absent


def read_all(bench: dict, workload: str, res: dict,
             strict: bool = True) -> tuple[dict, dict, dict]:
    ctx = context(res)
    metrics, absent = read_metrics(bench["per_layer"], workload, ctx, strict)
    if absent:
        print(json.dumps({"absent": absent}), flush=True)
    trace = ctx["trace"]
    top = lambda d, n: sorted(d.items(), key=lambda kv: -kv[1])[:n]  # noqa: E731
    print(json.dumps({"trace_names": {
        "lines": ctx["xplane_lines"], "modules": top(trace["modules"], 12),
        "host_spans": {k: [len(v), sum(b - a for a, b in v)]
                       for k, v in ctx["host_spans"].items()}}}), flush=True)
    return (metrics,
            {"busy_s": trace["busy_s"], "window_s": trace["window_s"]},
            {"device_ops": trace["device_ops"],
             "idle_gaps": trace["idle_gaps"]})
