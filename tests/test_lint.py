"""Tier-1 lint gate (round 16): the full ``ctmrlint`` rule set over
the real package must be clean — zero non-baselined violations, a
tight justified baseline, and a strict time/dependency budget (AST
only, no jax import, <10s) so the gate is cheap enough to never skip.

Also pins the CLI scripting contract: exit 0 clean / 1 violations /
2 error, ``--json`` output shape."""

import json
import pathlib
import subprocess
import sys
import time

from ct_mapreduce_tpu.analysis.engine import load_baseline, run_analysis

REPO = pathlib.Path(__file__).resolve().parent.parent
PKG = REPO / "ct_mapreduce_tpu"
BASELINE = REPO / "ctmrlint.baseline"
MAX_BASELINE_ENTRIES = 10


def test_package_lints_clean_within_budget():
    t0 = time.monotonic()
    live, suppressed, unused = run_analysis(PKG, baseline_path=BASELINE)
    wall = time.monotonic() - t0
    assert not live, (
        "ctmrlint violations (fix them or add a JUSTIFIED baseline "
        "entry to ctmrlint.baseline):\n"
        + "\n".join(f.render() for f in live))
    assert not unused, f"stale baseline entries (delete them): {unused}"
    assert wall < 10.0, f"lint gate took {wall:.1f}s (budget: <10s)"


def test_baseline_is_tight_and_justified():
    entries = load_baseline(BASELINE)  # raises on missing justification
    assert len(entries) <= MAX_BASELINE_ENTRIES, (
        f"baseline has {len(entries)} entries (cap "
        f"{MAX_BASELINE_ENTRIES}) — fix findings instead of "
        f"baselining them")
    for key, why in entries.items():
        assert len(why) >= 15, f"{key}: justification too thin: {why!r}"


def test_cli_clean_run_exit_0_json_and_no_jax():
    """One real subprocess run: exit code 0, --json shape, and the
    jax-free budget (the lint lane must not pay XLA startup)."""
    code = (
        "import sys, json\n"
        "from ct_mapreduce_tpu.analysis.cli import main\n"
        "rc = main(['ct_mapreduce_tpu', '--json'])\n"
        "assert 'jax' not in sys.modules, 'lint lane imported jax'\n"
        "sys.exit(rc)\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["counts"]["findings"] == 0
    assert doc["counts"]["unused_baseline"] == 0
    assert doc["counts"]["suppressed"] == len(load_baseline(BASELINE))
    for f in doc["suppressed"]:
        assert {"rule", "path", "line", "symbol", "message",
                "key"} <= set(f)


def test_cli_exit_codes_violations_and_error(tmp_path):
    """Exit 1 on findings, exit 2 on bad invocation — in-process (the
    CLI main is a plain function) to keep the gate fast."""
    from ct_mapreduce_tpu.analysis.cli import main

    bad_pkg = tmp_path / "pkgx"
    bad_pkg.mkdir()
    (bad_pkg / "bad.py").write_text(
        "import threading\n"
        "class T:\n"
        "    def __init__(self):\n"
        "        self._oops = threading.Lock()\n")
    assert main([str(bad_pkg), "--baseline", "none",
                 "--rules", "lock-order"]) == 1
    assert main([str(tmp_path / "does-not-exist")]) == 2
    assert main([str(bad_pkg), "--rules", "no-such-rule"]) == 2
    assert main([str(bad_pkg), "--baseline",
                 str(tmp_path / "missing.baseline")]) == 2


def test_cli_rule_selection_and_listing(capsys):
    from ct_mapreduce_tpu.analysis.cli import main

    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out.split()
    assert {"lock-order", "donation-safety", "determinism",
            "jit-purity", "metric-registry", "span-registry",
            "config-parity"} == set(out)
    # Single-rule run over the real package stays clean too.
    assert main([str(PKG), "--rules", "lock-order",
                 "--baseline", "none"]) == 0
