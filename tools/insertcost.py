"""Insert-only cost probe: the dedup-table insert under the bench's
timing contract (jitted fori_loop sweeps + synchronous value read).

Isolates the table insert from the rest of the fused step so insert
formulation changes iterate without the full ~200s step compile: keys
are synthesized on device (SHA-free — four counter-derived words mixed
with an epoch), all-fresh per sweep, exactly the access pattern of the
headline's insert leg.

Run:  python tools/insertcost.py [batch] [log2_cap]
Env:  CTMR_TABLE=bucket|open, CT_IC_EXEC_SECS, CT_IC_SWEEPS
"""

from __future__ import annotations

import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main() -> None:
    import jax
    import jax.numpy as jnp

    if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
        jax.config.update("jax_platforms", "cpu")

    from ct_mapreduce_tpu.ops import buckettable, hashtable, pipeline

    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 1 << 20
    log2_cap = int(sys.argv[2]) if len(sys.argv) > 2 else 26
    cap = 1 << log2_cap
    exec_target_s = float(os.environ.get("CT_IC_EXEC_SECS", "4.0"))

    if os.environ.get("CTMR_TABLE", "bucket").strip().lower() == "open":
        mk_table = hashtable.make_table
    else:
        mk_table = buckettable.make_table

    t0 = time.perf_counter()
    dev = jax.devices()[0]
    say(f"device: {dev.platform} ({dev.device_kind}) acquired in "
        f"{time.perf_counter() - t0:.1f}s; batch={batch} cap=2^{log2_cap}")

    lane = np.arange(batch, dtype=np.uint32)
    meta = jax.device_put(np.zeros((batch,), np.uint32))
    valid = jax.device_put(np.ones((batch,), bool))
    lane_dev = jax.device_put(lane)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def mega(table, acc, epoch_base, n_sweeps, lane, meta, valid):
        def keygen(e):
            # 4 well-mixed words from (epoch, lane): unique per sweep,
            # uniform over buckets — the all-fresh worst case.
            a = lane * jnp.uint32(0x9E3779B9) + e * jnp.uint32(0x85EBCA6B)
            b = (a ^ (a >> 15)) * jnp.uint32(0xC2B2AE35)
            c = (b ^ (b >> 13)) * jnp.uint32(0x27D4EB2F)
            d = (c ^ (c >> 16)) * jnp.uint32(0x165667B1)
            return jnp.stack([a ^ e, b, c, d], axis=1)

        def body(s, carry):
            table, acc = carry
            keys = keygen((epoch_base + s).astype(jnp.uint32))
            table, unknown, ovf = pipeline.table_insert(
                table, keys, meta, valid)
            return table, (acc + unknown.sum(dtype=jnp.int32)
                           + ovf.sum(dtype=jnp.int32))

        return jax.lax.fori_loop(0, n_sweeps, body, (table, acc))

    fetch = jax.jit(lambda a: a + a.dtype.type(0))
    table = mk_table(cap)
    acc = jax.device_put(np.int32(0))

    t0 = time.perf_counter()
    table, acc = mega(table, acc, np.uint32(0), np.int32(1),
                      lane_dev, meta, valid)
    int(fetch(acc))
    say(f"compile+warmup: {time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    table, acc = mega(table, acc, np.uint32(1), np.int32(1),
                      lane_dev, meta, valid)
    int(fetch(acc))
    per_sweep = max(time.perf_counter() - t0, 1e-4)
    budget = max(2, int(cap * 0.45) // batch - 3)
    n = max(2, min(int(exec_target_s / per_sweep), budget, 200))
    t0 = time.perf_counter()
    table, acc = mega(table, acc, np.uint32(2), np.int32(n),
                      lane_dev, meta, valid)
    int(fetch(acc))
    dt = (time.perf_counter() - t0) / n
    total = int(fetch(acc))
    load = total / (getattr(table, "capacity", cap))
    say(f"insert  {dt * 1e3:9.2f} ms/sweep  {dt / batch * 1e9:8.1f} ns/entry"
        f"  ({n} sweeps; end load {load:.1%}; fresh+ovf={total})")
    expect = (n + 2) * batch
    if total != expect:
        say(f"WARNING: fresh+overflow {total} != stamped {expect} "
            "(duplicate keygen or dropped lanes)")


if __name__ == "__main__":
    main()
