"""Bench failure-mode contract: one parseable JSON line, always.

The contract under test (bench.py docstring "Robustness contract"):

- a platform that is not the TPU → rc=1 plus a structured
  ``{"value": 0, "error": ...}`` line (the benchmark never measures a
  CPU under a chip metric's name);
- a watchdog firing mid-measurement → rc=1 plus the PARTIAL measured
  rate (``"error": "partial: watchdog ..."``), never a bare 0;
- a child that dies without a word → the launcher parent (which never
  imports jax, so it never holds the chip its child needs) emits the
  partial rate it saw in the child's heartbeat.
"""

import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _one_json_line(stdout: str, stderr: str = "") -> dict:
    lines = [ln for ln in stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, (
        f"bench must print exactly one stdout line; got {stdout!r} "
        f"(stderr tail: {stderr[-500:]!r})"
    )
    return json.loads(lines[0])


def _run(argv: list, env_extra: dict, timeout: float):
    env = dict(os.environ)
    env.update(env_extra)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.environ.get("PYTHONPATH", ""), REPO) if p
    )
    proc = subprocess.run(
        [sys.executable] + argv, capture_output=True, text=True,
        timeout=timeout, env=env, cwd=REPO,
    )
    return proc.returncode, _one_json_line(proc.stdout, proc.stderr)


def test_non_tpu_platform_emits_structured_error():
    """The real ``bench.py``, exactly as the driver runs it, on a
    machine whose JAX platform is the CPU: rc=1 and a driver-parseable
    error line that names the platform it found."""
    rc, j = _run([os.path.join(REPO, "bench.py")],
                 {"JAX_PLATFORMS": "cpu", "CT_BENCH_WATCHDOG_SECS": "60"},
                 timeout=120)
    assert rc == 1
    assert j["metric"] == "ct_entries_per_sec_per_chip"
    assert j["value"] == 0
    assert j["unit"] == "entries/s/chip"
    assert "needs a TPU" in j["error"] and "platform=cpu" in j["error"]


def test_watchdog_mid_measurement_emits_partial_rate():
    """A watchdog that fires after ≥1 timed sweep must report the
    partial measured rate, not 0."""
    probe = textwrap.dedent("""
        import time
        import bench

        bench.start_watchdog(0.5)
        now = time.monotonic()
        bench._progress.update(processed=32768, t0=now - 2.0, last_sync=now)
        time.sleep(30)  # the watchdog force-exits long before this
    """)
    rc, j = _run(["-c", probe], {}, timeout=60)
    assert rc == 1
    assert j["metric"] == "ct_entries_per_sec_per_chip"
    assert 16000 < j["value"] < 16800, j
    assert j["error"].startswith("partial: watchdog")
    assert j["vs_baseline"] > 0


def test_silent_child_death_emits_partial_rate(tmp_path, monkeypatch,
                                               capsys):
    """The launcher's insurance: a child killed with SIGKILL after a
    timed chunk was logged must still yield one stdout JSON line
    carrying the partial measured rate."""
    import bench

    child = tmp_path / "dying_child.py"
    child.write_text(textwrap.dedent("""
        import os, signal, sys
        print("chunk 1: 32768 entries in 2.0s cumulative 16,384 entries/s",
              file=sys.stderr, flush=True)
        os.kill(os.getpid(), signal.SIGKILL)
    """))
    monkeypatch.setattr(bench, "__file__", str(child))
    monkeypatch.setattr(bench, "_emitted", False)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    rc = bench.launcher()
    j = _one_json_line(capsys.readouterr().out)
    assert rc == 1
    assert j["metric"] == "ct_entries_per_sec_per_chip"
    assert j["value"] == 16384.0, j
    assert "without emitting" in j["error"]
    assert j["vs_baseline"] > 0
