"""span-registry: every span name handed to the tracer is documented,
every documented span name has a call site.

The round-23 generalization of the ``metric-registry`` rule to the
tracer surface: collect every ``span(...)``/``instant(...)``/
``record_span(...)`` call site
in the package (literal first argument becomes the name, a dynamic one
becomes ``*``) and diff against the backtick-quoted bullets of the
``## Trace spans`` sections in ``docs/METRICS.md`` — the same file,
split by section so span names and metric keys each get exactly one
registry. Wildcards match both directions, same as metric keys:
``tools/traceview.py --merge`` timelines and the benchmark's span-ring
readers key on these names, so an undocumented span is dashboard drift just
like an undocumented counter.
"""

from __future__ import annotations

import ast

from ct_mapreduce_tpu.analysis.engine import Checker, Ctx, Project
from ct_mapreduce_tpu.analysis.metric_registry import (
    DOC_RELPATH,
    bullet_keys,
    key_matches,
)

EMIT_FUNCS = {"span", "instant", "record_span"}
# The tracer API itself: the names it passes through are its callers',
# not call sites. A literal there is a span the tracer records itself
# (the GIL probe's).
EXCLUDE_MODULES = ("ct_mapreduce_tpu/telemetry/trace.py",)


def documented_spans(doc_text: str) -> set[str]:
    """Backtick-quoted names from the ``## Trace spans`` sections."""
    return bullet_keys(doc_text, span_sections=True)


class SpanRegistryChecker(Checker):
    name = "span-registry"

    def __init__(self) -> None:
        super().__init__()
        # span name -> ["path:line", ...]
        self.call_sites: dict[str, list[str]] = {}

    def visit_Call(self, node: ast.Call, ctx: Ctx) -> None:
        fn = node.func
        name = (fn.attr if isinstance(fn, ast.Attribute)
                else fn.id if isinstance(fn, ast.Name) else None)
        if name not in EMIT_FUNCS or not node.args:
            return
        arg = node.args[0]
        literal = isinstance(arg, ast.Constant) and isinstance(arg.value, str)
        if ctx.module.relpath in EXCLUDE_MODULES and not literal:
            return
        span_name = arg.value if literal else "*"
        where = f"{ctx.module.relpath}:{node.lineno}"
        self.call_sites.setdefault(span_name, []).append(where)

    def finish(self, project: Project) -> None:
        doc_path = project.repo_root / DOC_RELPATH
        if not doc_path.exists():
            self.report(DOC_RELPATH, 0, "missing",
                        "docs/METRICS.md not found — the span-name "
                        "registry shares the metric registry file")
            return
        docs = documented_spans(doc_path.read_text())
        if not docs:
            self.report(DOC_RELPATH, 0, "empty",
                        "docs/METRICS.md has no `## Trace spans` "
                        "bullets — section renamed?")
            return
        for name, sites in sorted(self.call_sites.items()):
            if not any(key_matches(name, d) for d in docs):
                path, _, line = sites[0].rpartition(":")
                self.report(
                    path, int(line), name,
                    f"span name `{name}` traced ({', '.join(sites)}) "
                    f"but missing from the `## Trace spans` sections "
                    f"of docs/METRICS.md — timelines and occupancy "
                    f"tooling key on these names")
        for d in sorted(docs):
            if not any(key_matches(name, d) for name in self.call_sites):
                self.report(
                    DOC_RELPATH, 0, f"stale:{d}",
                    f"docs/METRICS.md lists span `{d}` but no call "
                    f"site traces it — deleting a span must update "
                    f"the registry too")
