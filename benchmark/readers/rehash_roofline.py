"""The growth's rehash on the device: its share of its roofline, in
percent (the least time the chip could take for the growths the profile
shows inside the window: ``rehash_cost.table_double`` of the
configuration's ``tableBits``, over the published memory peak, a
growth's programs counted as one call of the module named first in
``match``), or the device seconds of its programs. The growths are
counted from the trace and their bytes come from the configuration, so
a program that moved less than the two tables would read over 100, not
faster.

params: ``match`` (substrings of the names of the XLA modules that are
the growth's programs; ``docs/METRICS.md`` says which names the program
keeps; the first is the one a growth runs exactly once), ``what``
(``roofline_pct``, the default, or ``seconds``).

Not in this program (``layers.ABSENT``) where its span ring holds no
span of the family ``grow.``: a program that grows its table through
the host has no such program on the device. Nothing to read (None)
where it has the family and the window's profile holds none of the
modules: the growth fell outside the window, or the program was
renamed.
"""

from __future__ import annotations

import peaks
import rehash_cost
from layers import ABSENT
from readers import span_ring


def read(params: dict, ctx: dict):
    ring = ctx["ring"] if "ring" in ctx else span_ring.live_ring()
    if ring is None:
        return ABSENT
    spans = [e for e in ring["events"] if e.get("ph") == "X"]
    if span_ring.family_absent(ring, spans, "grow.rehash"):
        return ABSENT
    trace = ctx["trace"]
    match = params["match"]
    seconds = sum(v for k, v in trace["modules"].items()
                  if any(m in k for m in match))
    calls = sum(v for k, v in trace["module_calls"].items() if match[0] in k)
    if seconds <= 0.0 or not calls:
        return None
    if params.get("what", "roofline_pct") == "seconds":
        return seconds
    bits = int(ctx["config"]["directives"]["tableBits"])
    least = calls * rehash_cost.table_double(bits)["hbm_bytes"] / peaks.peak(
        ctx["device"]["kind"], "hbm_bytes_per_s")
    return 100.0 * least / seconds
