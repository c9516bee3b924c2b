"""How far apart the logs of a round stand when the window's last batch
is folded, in percent of one log's entries: the entries the log server
had served of the furthest log less those of the hindmost, at
``t_folded``, from the server's page stamps (``out["pages"]``: log,
start, count and the instant the response's first byte was written,
every page since the log opened). Three downloaders behind one channel
take turns as the channel's ``put`` wakes them; a skew that grows says
one of them is starved. One log reads 0.

A log's entries are what the round served of it, first page to last
(the round is over when this is read).
"""

from __future__ import annotations


def read(params: dict, ctx: dict):
    out = ctx["out"]
    first: dict[int, int] = {}
    last: dict[int, int] = {}
    at_fold: dict[int, int] = {}
    for log, start, count, _t_req, t_resp, _t_done in out["pages"]:
        first[log] = min(first.get(log, start), start)
        last[log] = max(last.get(log, 0), start + count)
        if t_resp <= out["t_folded"]:
            at_fold[log] = max(at_fold.get(log, 0), start + count)
    if not last:
        return None
    served = [at_fold.get(log, first[log]) - first[log] for log in last]
    per_log = max(last[log] - first[log] for log in last)
    if per_log <= 0:
        return None
    return 100.0 * (max(served) - min(served)) / per_log
