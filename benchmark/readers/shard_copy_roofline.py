"""``copy_roofline.py`` for a table sharded over a mesh: the replica
copy's share of its roofline, in percent. The profile has a plane a
chip, and a plane's call of the copy moves that chip's shard, not the
table: the least time is the calls counted over all planes times
``snapshot_cost.table_copy`` of a SHARD's bits (the configuration's
``tableBits`` less log2 of the shards its ``meshShape`` names), over the
published memory peak; the seconds are the copies' device time summed
over the planes, so the share is that of a chip's copy. The bytes stay
the configuration's: a program that copied less than its shard would
read over 100, not faster.

params: ``match`` (substring of the name of the XLA module that is the
copy: ``docs/METRICS.md`` says which name the program keeps).

Without ``meshShape`` the table is one chip's and this reads what
``copy_roofline.py`` reads.
"""

import peaks
import snapshot_cost


def shards(config: dict) -> int:
    """``meshShape = shard:4`` names four; every axis multiplies."""
    n = 1
    for axis in str(config["directives"].get("meshShape", "")).split(","):
        if axis.strip():
            n *= int(axis.rsplit(":", 1)[1])
    return n


def read(params: dict, ctx: dict):
    trace = ctx["trace"]
    seconds = sum(v for k, v in trace["modules"].items()
                  if params["match"] in k)
    calls = sum(v for k, v in trace["module_calls"].items()
                if params["match"] in k)
    if seconds <= 0.0:
        return None
    n = shards(ctx["config"])
    if n & (n - 1):
        raise ValueError(f"{n} shards: a shard's bits are not whole")
    bits = int(ctx["config"]["directives"]["tableBits"]) - (n.bit_length() - 1)
    least = calls * snapshot_cost.table_copy(bits)["hbm_bytes"] / peaks.peak(
        ctx["device"]["kind"], "hbm_bytes_per_s")
    return 100.0 * least / seconds
