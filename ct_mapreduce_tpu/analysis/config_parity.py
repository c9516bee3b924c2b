"""config-parity: one directive, four surfaces, zero drift.

A configuration directive exists in four places that historically
drifted independently: the ``_DIRECTIVES`` parse table in
``config/config.py``, the self-documenting ``usage()`` text, the
``CTMR_*`` env layer inside the subsystem ``resolve_*`` functions,
and the operator-facing MIGRATING.md. This rule diffs them:

- every parsed directive must appear in ``usage()``;
- every ``name =`` line in ``usage()`` must be a parsed directive
  (no ghost documentation);
- every TPU-native directive (not inherited from the Go reference —
  those are covered by reference docs) must appear in MIGRATING.md;
- every ``CTMR_*`` env var consulted by a ``resolve_*`` function must
  appear in MIGRATING.md (the env layer is API).

Round 18 (the platformProfile refactor) adds two surfaces: knob specs
(``Knob(...)`` declarations in config/profile.py's engine) carry the
env names that used to live inline in ``resolve_*`` bodies — their
``CTMR_*`` strings are collected the same way — and every profile
section resolved via ``resolve_section("<name>", ...)`` must be
documented in MIGRATING.md as ``knobs.<name>`` (the profile file
format is operator API too).
"""

from __future__ import annotations

import ast
import re

from ct_mapreduce_tpu.analysis.engine import Checker, Ctx, Project

CONFIG_RELPATH = "ct_mapreduce_tpu/config/config.py"
MIGRATING_RELPATH = "MIGRATING.md"

# Directives inherited 1:1 from the reference's config.go — their
# operator docs are the reference's; MIGRATING.md documents deltas.
REFERENCE_DIRECTIVES = frozenset({
    "offset", "limit", "logList", "numThreads", "logExpiredEntries",
    "runForever", "pollingDelayMean", "pollingDelayStdDev",
    "savePeriod", "issuerCNFilter", "certPath", "googleProjectId",
    "redisHost", "redisTimeout", "outputRefreshPeriod",
    "statsRefreshPeriod", "statsdHost", "statsdPort", "healthAddr",
})

_ENV_RE = re.compile(r"^CTMR_[A-Z0-9_]+$")


class ConfigParityChecker(Checker):
    name = "config-parity"

    def __init__(self) -> None:
        super().__init__()
        # env var -> first "path:line" inside a resolve_* function
        self.resolve_envs: dict[str, str] = {}
        # profile section -> first "path:line" of a resolve_section call
        self.profile_sections: dict[str, str] = {}
        self._resolve_stack = 0

    # -- collect CTMR_* envs inside resolve_* functions ------------------
    def visit_FunctionDef(self, node: ast.FunctionDef, ctx: Ctx) -> None:
        if not node.name.startswith("resolve_"):
            return
        for sub in ast.walk(node):
            if isinstance(sub, ast.Constant) and isinstance(
                    sub.value, str) and _ENV_RE.match(sub.value):
                self.resolve_envs.setdefault(
                    sub.value, f"{ctx.module.relpath}:{sub.lineno}")

    # -- collect CTMR_* envs from Knob specs + profile section names -----
    def visit_Call(self, node: ast.Call, ctx: Ctx) -> None:
        fn = node.func
        name = (fn.id if isinstance(fn, ast.Name)
                else fn.attr if isinstance(fn, ast.Attribute) else None)
        if name == "Knob":
            for arg in list(node.args) + [kw.value for kw in
                                          node.keywords]:
                if isinstance(arg, ast.Constant) and isinstance(
                        arg.value, str) and _ENV_RE.match(arg.value):
                    self.resolve_envs.setdefault(
                        arg.value, f"{ctx.module.relpath}:{arg.lineno}")
        elif name == "resolve_section":
            if node.args and isinstance(
                    node.args[0], ast.Constant) and isinstance(
                    node.args[0].value, str):
                self.profile_sections.setdefault(
                    node.args[0].value,
                    f"{ctx.module.relpath}:{node.lineno}")

    # -- diff the four surfaces ------------------------------------------
    @staticmethod
    def _directives(tree: ast.AST) -> dict[str, int]:
        """directive -> lineno from the _DIRECTIVES dict literal."""
        out: dict[str, int] = {}
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == "_DIRECTIVES"
                    for t in node.targets):
                if isinstance(node.value, ast.Dict):
                    for k in node.value.keys:
                        if isinstance(k, ast.Constant) and isinstance(
                                k.value, str):
                            out[k.value] = k.lineno
        return out

    @staticmethod
    def _usage_text(tree: ast.AST) -> str:
        chunks: list[str] = []
        for node in ast.walk(tree):
            if isinstance(node, ast.FunctionDef) and node.name == "usage":
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Constant) and isinstance(
                            sub.value, str):
                        chunks.append(sub.value)
        return "\n".join(chunks)

    def finish(self, project: Project) -> None:
        cfg = project.module(CONFIG_RELPATH)
        if cfg is None:
            self.report(CONFIG_RELPATH, 0, "missing",
                        "config module not found under the scanned root")
            return
        directives = self._directives(cfg.tree)
        if not directives:
            self.report(CONFIG_RELPATH, 0, "no-directives",
                        "_DIRECTIVES dict literal not found — parser "
                        "refactor? update config_parity.py")
            return
        usage = self._usage_text(cfg.tree)
        migrating_path = project.repo_root / MIGRATING_RELPATH
        migrating = (migrating_path.read_text()
                     if migrating_path.exists() else "")

        for d, line in sorted(directives.items()):
            if d not in usage:
                self.report(CONFIG_RELPATH, line, f"usage:{d}",
                            f"directive {d} is parsed but absent from "
                            f"usage() — operators discover directives "
                            f"there")
            if d not in REFERENCE_DIRECTIVES and d not in migrating:
                self.report(CONFIG_RELPATH, line, f"migrating:{d}",
                            f"TPU-native directive {d} undocumented in "
                            f"MIGRATING.md")

        # Ghost documentation: usage() lines shaped like directives.
        for m in re.finditer(r"^(\w+) = ", usage, re.MULTILINE):
            token = m.group(1)
            if token not in directives:
                self.report(CONFIG_RELPATH, 0, f"usage-unknown:{token}",
                            f"usage() documents '{token}' but no such "
                            f"directive is parsed")

        if not migrating:
            self.report(MIGRATING_RELPATH, 0, "missing",
                        "MIGRATING.md not found")
            return
        for env, where in sorted(self.resolve_envs.items()):
            if env not in migrating:
                self.report(
                    where.rpartition(":")[0],
                    int(where.rpartition(":")[2]),
                    f"migrating-env:{env}",
                    f"env var {env} (consulted by a resolve_* layer, "
                    f"{where}) undocumented in MIGRATING.md")
        for section, where in sorted(self.profile_sections.items()):
            if f"knobs.{section}" not in migrating:
                self.report(
                    where.rpartition(":")[0],
                    int(where.rpartition(":")[2]),
                    f"migrating-profile:{section}",
                    f"platformProfile section knobs.{section} "
                    f"(resolved at {where}) undocumented in "
                    f"MIGRATING.md")
