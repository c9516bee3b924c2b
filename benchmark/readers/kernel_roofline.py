"""A kernel's share of its roofline, in percent: the least time the chip
could take for the calls the profile shows inside the window (here
bytes over the published memory peak; see peaks.py for why that is the
only bound) over the device time of those calls.

params: ``match`` (substring of the kernel's op names), ``cost`` (a
function of kernel_cost.py, given the lanes), ``lanes_per_call`` (a
number, or ``batchSize`` for the configuration's).
"""

import kernel_cost
import peaks


def read(params: dict, ctx: dict):
    trace = ctx["trace"]
    seconds = sum(v for k, v in trace["ops"].items() if params["match"] in k)
    calls = sum(v for k, v in trace["op_calls"].items()
                if params["match"] in k)
    if seconds <= 0.0:
        return None
    lanes = params["lanes_per_call"]
    if lanes == "batchSize":
        lanes = int(ctx["config"]["directives"]["batchSize"])
    cost = getattr(kernel_cost, params["cost"])(calls * lanes)
    least = cost["hbm_bytes"] / peaks.peak(ctx["device"]["kind"],
                                           "hbm_bytes_per_s")
    return 100.0 * least / seconds
