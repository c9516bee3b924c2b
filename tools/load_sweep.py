"""Insert throughput vs table load factor, on hardware.

Per-chunk time grows as the table loads (4.92s → 7.12s by 36% load in
a July run): the headline rate is a function of load. This sweep measures entries/s at a ladder
of load factors so the grow-at threshold (TpuAggregator.grow_at,
default 0.7) is chosen from data, not folklore.

Method, as in bench.py: sweeps run inside a
jitted fori_loop (few device executions, each ~CT_SWEEP_EXEC_SECS),
every timed block ends with a synchronous device-value read.

Usage: python tools/load_sweep.py [log2_capacity] [loads...]
Writes one JSON line per load point on stdout.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax
    import jax.numpy as jnp


    from ct_mapreduce_tpu.core import packing
    from ct_mapreduce_tpu.ops import hashtable, pipeline
    from ct_mapreduce_tpu.utils import syncerts

    log2_cap = int(sys.argv[1]) if len(sys.argv) > 1 else 24
    loads = ([float(x) for x in sys.argv[2:]]
             if len(sys.argv) > 2 else [0.10, 0.25, 0.50, 0.75])
    capacity = 1 << log2_cap
    batch = int(os.environ.get("CT_SWEEP_BATCH", str(1 << 17)))
    pad_len = 1024
    exec_target_s = float(os.environ.get("CT_SWEEP_EXEC_SECS", "6.0"))
    timed_sweeps = int(os.environ.get("CT_SWEEP_TIMED", "8"))
    now_hour = 500_000

    dev = jax.devices()[0]
    print(f"device: {dev.platform} ({dev.device_kind}); "
          f"capacity=2^{log2_cap} batch={batch}", file=sys.stderr)

    tpl = syncerts.make_template()
    datas, lens = syncerts.build_device_batches(tpl, 1, batch, pad_len)
    issuer_idx = jax.device_put(np.zeros((batch,), np.int32))
    valid = jax.device_put(np.ones((batch,), bool))
    epoch_cols = tpl.serial_off + np.arange(4, 8, dtype=np.int32)

    # All device arrays are ARGUMENTS (closure over a committed buffer
    # permanently degrades dispatch on this stack — ARCHITECTURE.md).
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run_sweeps(table, fresh_acc, epoch_base, n_sweeps,
                   datas, lens, issuer_idx, valid):
        def body(s, carry):
            table, fresh_acc = carry
            e = (epoch_base + s).astype(jnp.uint32)
            eb = jnp.stack(
                [(e >> 24) & 0xFF, (e >> 16) & 0xFF, (e >> 8) & 0xFF,
                 e & 0xFF]).astype(jnp.uint8)
            data = datas[0].at[:, epoch_cols].set(eb[None, :])
            table, out = pipeline.ingest_core(
                table, data, lens[0], issuer_idx, valid,
                jnp.int32(now_hour), jnp.int32(packing.DEFAULT_BASE_HOUR),
                jnp.zeros((0, 32), jnp.uint8), jnp.zeros((0, 2), jnp.int32),
            )
            return table, fresh_acc + out.was_unknown.sum().astype(jnp.int32)

        return jax.lax.fori_loop(0, n_sweeps, body, (table, fresh_acc))

    _fetch = jax.jit(lambda a: a + a.dtype.type(0))

    # The shipping layout (CTMR_TABLE, default bucket) — load curves
    # must describe the table production runs.
    table = pipeline.make_table(capacity)
    capacity = getattr(table, "capacity", capacity)
    fresh = jax.device_put(np.int32(0))

    # Compile + calibrate with one sweep.
    t0 = time.perf_counter()
    table, fresh = run_sweeps(table, fresh, np.uint32(0), np.int32(1),
                              datas, lens, issuer_idx, valid)
    int(_fetch(fresh))
    print(f"compile+warm: {time.perf_counter() - t0:.1f}s", file=sys.stderr)
    t0 = time.perf_counter()
    table, fresh = run_sweeps(table, fresh, np.uint32(1), np.int32(1),
                              datas, lens, issuer_idx, valid)
    int(_fetch(fresh))
    per_sweep = max(time.perf_counter() - t0, 1e-4)
    chunk_sweeps = max(1, int(exec_target_s / per_sweep))
    print(f"calibration: {per_sweep * 1e3:.0f} ms/sweep → "
          f"chunk={chunk_sweeps}", file=sys.stderr)

    epoch = 2
    for target in loads:
        want_fill = int(target * capacity)
        # Fill (unmeasured) to the target load in chunked executions.
        prev_fill = -1
        while True:
            fill = int(_fetch(table.count))
            if fill == prev_fill:
                # Probe overflow plateaus the fill below pathological
                # targets; a stalled loop must break, not spin forever.
                print(f"fill stalled at {fill} ({fill / capacity:.0%}) "
                      f"short of {target:.0%}; measuring there",
                      file=sys.stderr)
                break
            prev_fill = fill
            need = (want_fill - fill) // batch
            if need < 1:
                break
            n = min(need, chunk_sweeps)
            table, fresh = run_sweeps(
                table, fresh, np.uint32(epoch), np.int32(n),
                datas, lens, issuer_idx, valid)
            int(_fetch(fresh))
            epoch += n
        fill = int(_fetch(table.count))
        # Timed block at this load: all-fresh inserts, synced read.
        t0 = time.perf_counter()
        done = 0
        while done < timed_sweeps:
            n = min(chunk_sweeps, timed_sweeps - done)
            table, fresh = run_sweeps(
                table, fresh, np.uint32(epoch), np.int32(n),
                datas, lens, issuer_idx, valid)
            int(_fetch(fresh))
            epoch += n
            done += n
        dt = time.perf_counter() - t0
        rate = timed_sweeps * batch / dt
        point = {
            "load": round(fill / capacity, 4),
            "entries_per_sec": round(rate, 1),
            "ms_per_batch": round(1e3 * dt / timed_sweeps, 2),
            "fill": fill,
            "capacity": capacity,
        }
        print(json.dumps(point), flush=True)
        print(f"load {point['load']:.0%}: {rate:,.0f} entries/s",
              file=sys.stderr)

    total = int(_fetch(table.count))
    expect = epoch * batch  # every sweep stamped unique serials
    missed = expect - total
    # At high load some unique inserts probe-overflow instead of
    # landing (production routes those to the exact host lane); they
    # surface here as fill shortfall. Below ~75% load expect ~0.
    print(f"final fill {total}/{expect} stamped; "
          f"{missed} probe-overflow spills", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
