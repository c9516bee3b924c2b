"""Test configuration: force an 8-device virtual CPU platform so
multi-chip sharding paths are exercised without TPU hardware (mirrors
the reference's pattern of gating real-Redis tests behind env vars,
/root/reference/storage/rediscache_test.go:16-28 — here the real-TPU
tests are the gated tier and the virtual mesh is the default)."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Tests run on the CPU; the real-TPU tier opts back in via
# CT_TPU_TESTS=1. Set before jax is imported.
if os.environ.get("CT_TPU_TESTS", "") == "":
    os.environ["JAX_PLATFORMS"] = "cpu"
# ct-fetch places a persistent compile cache inside the checkout
# (utils/compile_cache.py). The suite's hundreds of tiny CPU programs
# stay out of it; the tests of the cache itself turn it back on.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")


def compile_cache_env(path) -> dict:
    """Environment that turns the persistent compile cache back on, at
    ``path``, for child processes — caching every program, however
    quick its compile (the children compile tiny CPU programs)."""
    return {
        "JAX_COMPILATION_CACHE_DIR": str(path),
        "JAX_ENABLE_COMPILATION_CACHE": "true",
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1",
    }


import pytest  # noqa: E402

# Lock-order witness across the WHOLE suite (round 16): every
# concurrency test doubles as a race-order probe against the declared
# hierarchy (analysis/lockspec.py). Installed before any package
# module creates a lock; CTMR_LOCK_WITNESS=0 opts a run out.
# pytest_sessionfinish below fails the run on any order violation or
# cycle the witness observed.
os.environ.setdefault("CTMR_LOCK_WITNESS", "1")
from ct_mapreduce_tpu.analysis import witness as _lock_witness  # noqa: E402

_lock_witness.install()


@pytest.fixture(autouse=True)
def no_span_attributes_from_an_earlier_test():
    """Process-wide span attributes are the worker process's, not a
    test's: ``ingest/fleet.py`` stamps ``epoch`` on every later span and
    only ``ct_fetch.main`` takes it off, so a fleet test that ran in
    the same xdist worker before (which files share one is the
    scheduler's choice) put ``epoch`` into every span a later test
    compares whole (``test_multilog_round.py``, PR 37; four tests of
    ``test_lineage.py`` in one of PR 38's three whole runs)."""
    from ct_mapreduce_tpu.telemetry import trace

    trace.set_process_attrs(**dict.fromkeys(trace.get_process_attrs()))
    yield


@pytest.fixture
def benchmark_checkout(tmp_path, monkeypatch, request):
    """For the modules that run ``benchmark/tests`` in tier-1 (each names
    the module it imported from there ``theirs``). The harness keeps a run's
    state in ``<checkout>/.bench_work``, one a checkout, so rehearsals on
    two workers would wipe each other's. Each test gets a directory
    shaped like the checkout (links to ``benchmark/``, the package and
    ``BENCHMARK.json``) and the imported tests find their files from
    there: ``benchmark/`` computes every path from where its files lie.
    A rehearsal's child is a whole ``ct-fetch`` run of a one-chip cell,
    so it must not inherit the eight virtual devices asked for above (a
    mesh over them is another, far slower program)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    root = tmp_path / "checkout"
    root.mkdir()
    for name in ("benchmark", "ct_mapreduce_tpu", "BENCHMARK.json"):
        os.symlink(os.path.join(repo, name), root / name)
    bench = os.path.join(str(root), "benchmark")
    theirs = request.module.theirs
    monkeypatch.setattr(theirs, "ROOT", str(root))
    monkeypatch.setattr(theirs, "BENCH", bench)
    monkeypatch.setattr(theirs, "HERE", os.path.join(bench, "tests"))
    monkeypatch.setenv("XLA_FLAGS", " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f))


def on_tpu() -> bool:
    import jax

    return any(d.platform == "tpu" for d in jax.devices())


requires_tpu = pytest.mark.skipif(
    os.environ.get("CT_TPU_TESTS", "") == "", reason="set CT_TPU_TESTS=1 to run"
)


def pytest_configure(config):
    # pytest-timeout isn't in this image; register the mark so suites
    # that do install it get real timeouts and bare runs stay clean.
    config.addinivalue_line(
        "markers",
        "timeout(seconds): per-test timeout (enforced only when "
        "pytest-timeout is installed)",
    )
    config.addinivalue_line(
        "markers",
        "slow: outside the tier-1 budget (tier-1 runs -m 'not slow'); "
        "e.g. per-batch-width ECDSA kernel compiles",
    )


def pytest_sessionfinish(session, exitstatus):
    """The suite-wide lock-witness gate: zero order violations or
    cycles across everything the tier-1 run exercised."""
    w = _lock_witness.active()
    if w is None:
        return
    findings = w.findings()
    if not findings:
        return
    lines = ["", "=" * 70,
             "LOCK WITNESS: order violations / cycles observed:"]
    for v in findings:
        if v.get("kind") == "order":
            lines.append(
                f"  order: {v['held']} (rank {v['held_rank']}) held "
                f"while acquiring {v['acquiring']} "
                f"(rank {v['acquiring_rank']}) [{v['thread']}] at "
                f"{v['where']}")
        else:
            lines.append(
                f"  cycle: {' -> '.join(v.get('cycle', []))} "
                f"[{v['thread']}] at {v['where']}")
    lines.append("(hierarchy: ct_mapreduce_tpu/analysis/lockspec.py; "
                 "docs/ANALYSIS.md)")
    lines.append("=" * 70)
    print("\n".join(lines))
    session.exitstatus = 1
