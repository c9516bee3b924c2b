"""A mean over the spans of one name that END in the set-up: between
the call of ``ct_fetch.main`` and the log's opening, where the warm-up
round and what the program does at its end lie (``span_mean.py`` and
``span_count.py`` know the window, the drain and the round: all after
the opening). Of the spans' seconds, or of one of their numeric
arguments: ``span_mean.py``'s reading, with the set-up's two instants
handed to it as its window.

params: ``span`` (a name), ``arg`` (an argument to average; without it
the span's own seconds), ``scale``.

Not in this program (``layers.ABSENT``) and nothing to read (None) as
``span_mean.py`` has them: by the tracer, by the span's family, by the
drops. No span of the name in the set-up, or one without the argument,
is a span renamed or a set-up that did not run it: None.
"""

from __future__ import annotations

from readers import span_mean


def read(params: dict, ctx: dict):
    out = ctx["out"]
    setup = dict(out, t_first=out["t_main_called"], t_folded=out["t_open"])
    return span_mean.read(dict(params, phase="window"), dict(ctx, out=setup))
