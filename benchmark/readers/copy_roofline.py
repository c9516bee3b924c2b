"""The replica copy's share of its roofline, in percent: the least time
the chip could take for the copies the profile shows inside the window
(``snapshot_cost.table_copy`` of the configuration's ``tableBits``, over
the published memory peak: a copy does no arithmetic) over the device
time of those copies. The copies are counted from the trace and their
bytes come from the configuration, so a program that copied less than
the table would read over 100, not faster.

params: ``match`` (substring of the name of the XLA module that is the
copy: ``docs/METRICS.md`` says which name the program keeps).

A copy that straddles an end of the window counts as a call with the
part of its time that lies inside: one in some fifty.
"""

import peaks
import snapshot_cost


def read(params: dict, ctx: dict):
    trace = ctx["trace"]
    seconds = sum(v for k, v in trace["modules"].items()
                  if params["match"] in k)
    calls = sum(v for k, v in trace["module_calls"].items()
                if params["match"] in k)
    if seconds <= 0.0:
        return None
    bits = int(ctx["config"]["directives"]["tableBits"])
    least = calls * snapshot_cost.table_copy(bits)["hbm_bytes"] / peaks.peak(
        ctx["device"]["kind"], "hbm_bytes_per_s")
    return 100.0 * least / seconds
