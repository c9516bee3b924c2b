"""donation-safety: use-after-donate is a bug even when CPU hides it.

A buffer passed at a donated position of a ``*_donated`` jit entry
point (``ops/pipeline.py``'s ``donate_argnums``) is dead the moment
the call dispatches: XLA may alias its memory into the step's outputs
on real devices. CPU's XLA cannot alias these layouts and silently
falls back to copies — which is exactly why a use-after-donate
survives the whole CPU test tier and detonates on hardware. This rule
flags any read of a binding after it was passed at a donated position,
unless the binding was reassigned first (the canonical
``self.table, out = step(self.table, ...)`` idiom reassigns in the
same statement and is safe).

Donating callables are recognized by name (``*_donated``), including
locals aliased from them — the aggregator's backend-conditional
``step = (pipeline.ingest_step_preparsed if ... else
pipeline.ingest_step_preparsed_donated)`` donates on real devices, so the alias
is treated as donating (the conservative branch is the one that
bites). Donated positions come from :data:`DONATED_ARGNUMS`; unknown
``*_donated`` names default to position 0 (the table-first
convention).
"""

from __future__ import annotations

import ast
from typing import Optional

from ct_mapreduce_tpu.analysis.engine import Checker, Ctx

# Mirrors the donate_argnums of ops/pipeline.py's entry points. A new
# *_donated entry point not listed here is checked at position 0 only;
# list it to widen coverage.
DONATED_ARGNUMS: dict[str, tuple[int, ...]] = {
    "ingest_step_donated": (0, 1),
    "ingest_step_preparsed_donated": (0,),
}
DEFAULT_ARGNUMS: tuple[int, ...] = (0,)


def _tail_name(expr: ast.AST) -> Optional[str]:
    if isinstance(expr, ast.Attribute):
        return expr.attr
    if isinstance(expr, ast.Name):
        return expr.id
    return None


def _donated_names_in(expr: ast.AST) -> list[str]:
    """Every ``*_donated`` name referenced anywhere in ``expr``."""
    out = []
    for node in ast.walk(expr):
        n = _tail_name(node)
        if n is not None and n.endswith("_donated"):
            out.append(n)
    return out


def _binding_key(expr: ast.AST) -> Optional[str]:
    """Trackable binding: a plain name or a ``self.X`` attribute."""
    if isinstance(expr, ast.Name):
        return expr.id
    if isinstance(expr, ast.Attribute) and isinstance(
            expr.value, ast.Name) and expr.value.id == "self":
        return f"self.{expr.attr}"
    return None


def _assigned_keys(target: ast.AST) -> set[str]:
    keys: set[str] = set()
    for node in ast.walk(target):
        k = _binding_key(node)
        if k is not None:
            keys.add(k)
    return keys


class DonationChecker(Checker):
    name = "donation-safety"

    def visit_FunctionDef(self, node: ast.FunctionDef, ctx: Ctx) -> None:
        self._check_function(node, ctx)

    def visit_AsyncFunctionDef(self, node, ctx: Ctx) -> None:
        self._check_function(node, ctx)

    def _check_function(self, fn, ctx: Ctx) -> None:
        # Local donating aliases: X = <expr referencing *_donated>.
        aliases: dict[str, tuple[int, ...]] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and node.value is not None:
                donated = _donated_names_in(node.value)
                if not donated:
                    continue
                argnums: set[int] = set()
                for d in donated:
                    argnums.update(DONATED_ARGNUMS.get(d, DEFAULT_ARGNUMS))
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        aliases[t.id] = tuple(sorted(argnums))

        # Reassignment and loop structure for the exemptions below.
        assigns: list[tuple[int, set[str]]] = []  # (line, keys)
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign):
                keys = set()
                for t in node.targets:
                    keys |= _assigned_keys(t)
                assigns.append((node.lineno, keys))
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                assigns.append((node.lineno, _assigned_keys(node.target)))
            elif isinstance(node, (ast.For, ast.AsyncFor)):
                assigns.append((node.lineno, _assigned_keys(node.target)))

        loops = [n for n in ast.walk(fn)
                 if isinstance(n, (ast.For, ast.While, ast.AsyncFor))]

        def loop_of(lineno: int):
            best = None
            for lp in loops:
                end = getattr(lp, "end_lineno", lp.lineno)
                if lp.lineno <= lineno <= end:
                    if best is None or lp.lineno > best.lineno:
                        best = lp  # innermost
            return best

        def reassigned_between(key: str, a: int, b: int) -> bool:
            return any(a < line <= b and key in keys
                       for line, keys in assigns)

        # Donating calls and their donated bindings.
        # (call line, call end line, key, callee)
        donations: list[tuple[int, int, str, str]] = []
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            callee = _tail_name(node.func)
            if callee is None:
                continue
            if callee.endswith("_donated"):
                argnums = DONATED_ARGNUMS.get(callee, DEFAULT_ARGNUMS)
            elif callee in aliases:
                argnums = aliases[callee]
            else:
                continue
            end = getattr(node, "end_lineno", node.lineno)
            for i in argnums:
                if i < len(node.args):
                    key = _binding_key(node.args[i])
                    if key is not None:
                        donations.append((node.lineno, end, key, callee))

        if not donations:
            return

        relpath = ctx.module.relpath
        for call_line, call_end, key, callee in donations:
            # Reassigned in the very statement of the call (the
            # `self.table, out = step(self.table, ...)` idiom).
            if any(line == call_line and key in keys
                   for line, keys in assigns):
                continue
            call_loop = loop_of(call_line)
            if call_loop is not None:
                # Donation inside a loop whose body refreshes the
                # binding each iteration: textual order lies about
                # execution order; skip if any reassignment lives in
                # the same loop.
                end = getattr(call_loop, "end_lineno", call_loop.lineno)
                if any(call_loop.lineno <= line <= end and key in keys
                       for line, keys in assigns):
                    continue
            for node in ast.walk(fn):
                if node.__class__ is ast.Name:
                    if not (isinstance(node.ctx, ast.Load)
                            and node.id == key):
                        continue
                elif node.__class__ is ast.Attribute:
                    if not (isinstance(node.ctx, ast.Load)
                            and _binding_key(node) == key):
                        continue
                else:
                    continue
                read_line = node.lineno
                if read_line <= call_end:
                    continue  # the donating call's own argument lines
                if reassigned_between(key, call_line, read_line):
                    continue
                self.report(
                    relpath, read_line,
                    f"{fn.name}:{key}",
                    f"{key} read after being donated to {callee} "
                    f"(line {call_line}) without reassignment — "
                    f"use-after-donate aliases freed device memory "
                    f"on real hardware")
                break  # one finding per donation is enough
