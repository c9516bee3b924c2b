"""The persistent XLA compilation cache and where it is placed.

``utils/compile_cache.configure`` is the one place the program decides
that: ``JAX_COMPILATION_CACHE_DIR`` when the operator set it (JAX
reads the variable itself, nothing is set in code), else one fixed
directory inside the checkout. With a cache in place, a SECOND trace
of the same step shape is a cache HIT (observed through jax's own
monitoring events), not a recompile.

The hit probe runs in a SUBPROCESS: its ``jax.clear_caches()`` —
required to prove the persistent hit — would otherwise wipe every
in-memory executable of the whole tier-1 process mid-suite.
"""

import os
import subprocess
import sys

import pytest

from tests.conftest import compile_cache_env

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = r"""
import os, sys
import numpy as np
import jax

from ct_mapreduce_tpu.utils import compile_cache

cache_dir = os.environ["JAX_COMPILATION_CACHE_DIR"]
assert compile_cache.configure() == cache_dir
assert jax.config.jax_compilation_cache_dir == cache_dir

from jax._src import monitoring

events = []
monitoring.register_event_listener(lambda name, **kw: events.append(name))

from ct_mapreduce_tpu.core import packing
from ct_mapreduce_tpu.ops import pipeline

# A real (small) pre-parsed step shape — the same jit'd program the
# aggregator dispatches.
s = packing.MAX_SERIAL_BYTES

def step(table):
    return pipeline.ingest_step_preparsed(
        table, np.zeros((1, 64, s), np.uint8),
        np.zeros((1, 64), np.int32),
        np.full((1, 64), packing.DEFAULT_BASE_HOUR + 1, np.int32),
        np.zeros((1, 64), np.int32), np.ones((1, 64), bool),
        np.int32(packing.DEFAULT_BASE_HOUR),
        max_probes=4, flag_cap=64,
    )

table, out = step(pipeline.make_table(1 << 10))
np.asarray(out.packed)
assert any(os.scandir(cache_dir)), "no cache entry written"
first_hits = sum(1 for e in events if "cache_hit" in e)

# Drop every in-memory executable; the SAME shape must come back from
# the persistent cache, not a recompile.
jax.clear_caches()
table, out = step(pipeline.make_table(1 << 10))
np.asarray(out.packed)
second_hits = sum(1 for e in events if "cache_hit" in e)
assert second_hits > first_hits, (
    "no persistent-cache hit on the second trace "
    f"(events: {sorted(set(events))})")
print("CACHE-HIT-OK")
"""


@pytest.mark.timeout(120)
def test_second_trace_of_same_step_shape_is_cache_hit(tmp_path):
    env = dict(os.environ)
    env.update(compile_cache_env(tmp_path))
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.environ.get("PYTHONPATH", ""), REPO) if p)
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=110,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "CACHE-HIT-OK" in proc.stdout, (proc.stdout,
                                           proc.stderr[-500:])


def test_cache_is_placed_from_outside_or_at_one_fixed_path(monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, no directory is set in
    code; unset, it is <checkout>/.jax_cache — the same on every call."""
    import jax

    from ct_mapreduce_tpu.utils import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.configure() == "/somewhere/else"
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.configure() == fixed
    assert compile_cache.configure() == fixed
    assert updates == [("jax_compilation_cache_dir", fixed)] * 2
