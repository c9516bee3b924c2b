"""CI coverage for bench.py's e2e replay leg.

The e2e leg is the only place the three implementations — device
pipeline, byte-exact host lane, and the rediscache path over a real
TCP socket — are parity-checked against each other on one stream
(BASELINE config #4's gate). Locking it into the suite means a parity
regression fails CI, not just a hardware bench run."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.mark.timeout(600)
def test_bench_e2e_three_way_parity(monkeypatch):
    monkeypatch.setenv("CT_BENCH_E2E_BATCH", "256")
    monkeypatch.setenv("CT_BENCH_E2E_BATCHES", "2")
    # Keep this smoke test off the real TPU even outside
    # pytest/conftest.
    import jax

    if os.environ.get("CT_TPU_TESTS", "") == "":
        jax.config.update("jax_platforms", "cpu")
    import bench

    out = bench.run_e2e()
    assert out["e2e_entries"] == 512
    assert out["e2e_entries_per_sec"] > 0
