"""Per-stage cost of the fused step, bench-methodology edition.

This probe times each stage the way bench.py times the headline: the stage runs inside a jitted
`lax.fori_loop` sweep whose input is re-stamped per sweep (so nothing
is loop-invariant), accumulates a scalar that depends on every stage
output (so nothing is dead), and every chunk ends with a synchronous
device-value read. Stage deltas then give real per-stage costs:

  read    — one full HBM pass over the stamped uint8[B, L] batch
  pack    — + word-pack into uint32 rows
  parse   — + the DER walker (offsets, lengths, flags)
  serial  — + serial TLV gather to uint8[B, 46]
  sha     — + fingerprint block build + SHA-256
  lanes   — the full communication-free prefix (local_lanes)
  full    — ingest_core (adds the dedup-table insert, donated state)
  preparsed — the pre-parsed lane's whole device step (fingerprint +
            insert + compact readback from host-extracted sidecars;
            compare against `full` — the delta is what the host-side
            sidecar extraction buys the device)
  decode  — the HOST feed (native wire decode + sidecar extraction),
            swept over intra-chunk thread counts {1, 2, 4, cpu_count}
            with byte-exact parity asserted at each point; ns/entry
            per thread count is the host-feed scaling curve
            (CT_SC_DECODE_N overrides the wire batch size).
  dispatch — per-chunk Python-dispatch + H2D overhead of the staged
            device queue: the same 8 chunks run as 8/K resident
            envelopes at K ∈ {1, 2, 4, 8} chunks/dispatch
            (pipeline.staged_core), each dispatch paying one
            device_put + one jit call; byte parity of the packed
            readbacks and the final table is asserted against K=1.
            The wall delta across K is the per-dispatch toll that
            staging amortizes (CT_SC_DISPATCH_B overrides the chunk
            lane count).
  ckpt    — checkpoint-plane walls (round 22): full ck01 save vs
            incremental CTMRCK02 tick at churn {0.1%, 1%, 10%} of the
            fixture, restore wall at several chain depths; restored-
            state parity (tune.harness.ckpt_state_digest) asserted at
            every point against a ck01 oracle (CT_SC_CKPT_ENTRIES /
            _BITS / _CHURN / _DEPTHS override the fixture and sweeps).
  verify  — the batched ECDSA-P256 verification kernel
            (ops/ecdsa.verify_p256) at B ∈ {256, 1024, 4096}:
            ns/signature per batch width on a mixed valid/invalid
            corpus, verdict parity asserted against the pure-python
            host verifier at every width. The curve is the
            amortized-dispatch story — per-op overhead inside the
            256-bit ladder is fixed per op, so wider batches spread
            it over more lanes (CT_SC_VERIFY_B overrides the width
            list, comma-separated).

Run:  python tools/stagecost.py [batch] [stage ...]
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

    from ct_mapreduce_tpu.core import packing
    from ct_mapreduce_tpu.ops import buckettable, der_kernel, hashtable, pipeline
    from ct_mapreduce_tpu.utils import syncerts

    # The `full` stage builds whichever table layout the aggregator
    # would (CTMR_TABLE, default bucket) — ingest_core dispatches.
    if os.environ.get("CTMR_TABLE", "bucket").strip().lower() == "open":
        mk_table = hashtable.make_table
    else:
        mk_table = buckettable.make_table

    batch = int(sys.argv[1]) if len(sys.argv) > 1 else 1 << 20
    only = set(sys.argv[2:])
    pad_len = int(os.environ.get("CT_SC_PADLEN", "1024"))
    cap = 1 << int(os.environ.get("CT_SC_LOG2_CAP", "26"))
    exec_target_s = float(os.environ.get("CT_SC_EXEC_SECS", "4.0"))

    t0 = time.perf_counter()
    dev = jax.devices()[0]
    say(f"device: {dev.platform} ({dev.device_kind}) acquired in "
        f"{time.perf_counter() - t0:.1f}s; batch={batch} pad={pad_len}")

    tpl = syncerts.make_template()
    datas, lens = syncerts.build_device_batches(tpl, 1, batch, pad_len)
    issuer_idx = jax.device_put(np.zeros((batch,), np.int32))
    valid = jax.device_put(np.ones((batch,), bool))
    epoch_cols = tpl.serial_off + np.arange(4, 8, dtype=np.int32)
    now_hour = 500_000
    no_cn = np.zeros((0, 32), np.uint8)
    no_cn_lens = np.zeros((0, 2), np.int32)

    def stamp(data, e):
        eb = jnp.stack(
            [(e >> 24) & 0xFF, (e >> 16) & 0xFF, (e >> 8) & 0xFF, e & 0xFF]
        ).astype(jnp.uint8)
        return data.at[:, epoch_cols].set(eb[None, :])

    # Each stage maps the stamped batch to a uint32 scalar that depends
    # on every output it claims to compute (keeps the work live under
    # DCE while adding only a reduce).
    def s_read(data, length):
        return data.astype(jnp.uint32).sum()

    def s_pack(data, length):
        return der_kernel.pack_rows(data).words.sum()

    def s_pack2(data, length):
        # Experimental formulation: bitcast u8[B, L] -> u32[B, L/4]
        # (little-endian grouping) + in-register byteswap to the
        # big-endian words pack_rows produces via strided slices.
        # MEASURED SLOWER on v5e (36 vs 22 ns/entry standalone,
        # 2026-07-31) — kept as the recorded negative result; the
        # strided-slice pack_rows stays the shipping formulation.
        le = jax.lax.bitcast_convert_type(
            data.reshape(data.shape[0], -1, 4), jnp.uint32)
        be = ((le & 0xFF) << 24) | ((le & 0xFF00) << 8) \
            | ((le >> 8) & 0xFF00) | (le >> 24)
        return be.sum()

    def _parse(data, length):
        rows = der_kernel.pack_rows(data)
        p = der_kernel.parse_certs_rows(rows, length, scan_issuer_cn=False)
        return rows, p

    def s_parse(data, length):
        _, p = _parse(data, length)
        return (
            p.serial_off + p.serial_len + p.not_after_hour
            + p.ok.astype(jnp.int32) + p.is_ca.astype(jnp.int32)
            + p.crldp_off + p.issuer_off
        ).astype(jnp.uint32).sum()

    def s_serial(data, length):
        rows, p = _parse(data, length)
        serials, fits = der_kernel.gather_serials_rows(
            rows, p.serial_off, p.serial_len, packing.MAX_SERIAL_BYTES)
        return (serials.astype(jnp.uint32).sum()
                + fits.astype(jnp.uint32).sum()
                + p.not_after_hour.astype(jnp.uint32).sum())

    def s_sha(data, length):
        rows, p = _parse(data, length)
        serials, fits = der_kernel.gather_serials_rows(
            rows, p.serial_off, p.serial_len, packing.MAX_SERIAL_BYTES)
        fps = pipeline.fingerprints(
            issuer_idx, p.not_after_hour, serials, p.serial_len)
        return fps.sum() + fits.astype(jnp.uint32).sum()

    def s_lanes(data, length):
        lanes = pipeline.local_lanes(
            data, length, issuer_idx, valid, jnp.int32(now_hour),
            jnp.int32(packing.DEFAULT_BASE_HOUR), no_cn, no_cn_lens,
            packing.MAX_ISSUERS)
        return (lanes.fps.sum() + lanes.meta.sum()
                + lanes.insertable.astype(jnp.uint32).sum()
                + lanes.serials.astype(jnp.uint32).sum())

    def run_stage(name, stage_fn):
        @functools.partial(jax.jit, donate_argnums=(0,))
        def mega(acc, n_sweeps, datas, lens):
            def body(s, acc):
                data = stamp(datas[0], acc % jnp.uint32(1 << 20)
                             + jnp.uint32(s))
                return acc + stage_fn(data, lens[0])
            return jax.lax.fori_loop(0, n_sweeps, body, acc)

        fetch = jax.jit(lambda a: a + jnp.uint32(0))
        acc = jax.device_put(np.uint32(0))
        t0 = time.perf_counter()
        acc = mega(acc, np.int32(1), datas, lens)
        int(fetch(acc))
        say(f"  {name}: compile+warmup {time.perf_counter() - t0:.1f}s")
        t0 = time.perf_counter()
        acc = mega(acc, np.int32(1), datas, lens)
        int(fetch(acc))
        per_sweep = max(time.perf_counter() - t0, 1e-4)
        n = max(2, min(int(exec_target_s / per_sweep), 200))
        t0 = time.perf_counter()
        acc = mega(acc, np.int32(n), datas, lens)
        int(fetch(acc))
        dt = (time.perf_counter() - t0) / n
        say(f"{name:7s} {dt * 1e3:9.2f} ms/sweep  "
            f"{dt / batch * 1e9:8.1f} ns/entry  ({n} sweeps)")
        return dt

    def run_full():
        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def mega(table, acc, n_sweeps, datas, lens, issuer_idx, valid):
            def body(s, carry):
                table, acc = carry
                data = stamp(datas[0], acc + jnp.uint32(s))
                table, out = pipeline.ingest_core(
                    table, data, lens[0], issuer_idx, valid,
                    jnp.int32(now_hour),
                    jnp.int32(packing.DEFAULT_BASE_HOUR), no_cn, no_cn_lens)
                return table, acc + out.was_unknown.sum().astype(jnp.uint32)
            return jax.lax.fori_loop(0, n_sweeps, body, (table, acc))

        fetch = jax.jit(lambda a: a + jnp.uint32(0))
        table = mk_table(cap)
        acc = jax.device_put(np.uint32(0))
        t0 = time.perf_counter()
        table, acc = mega(table, acc, np.int32(1), datas, lens,
                          issuer_idx, valid)
        int(fetch(acc))
        say(f"  full: compile+warmup {time.perf_counter() - t0:.1f}s")
        t0 = time.perf_counter()
        table, acc = mega(table, acc, np.int32(1), datas, lens,
                          issuer_idx, valid)
        int(fetch(acc))
        per_sweep = max(time.perf_counter() - t0, 1e-4)
        budget = max(1, int(cap * 0.5) // batch - 3)
        n = max(2, min(int(exec_target_s / per_sweep), budget, 200))
        t0 = time.perf_counter()
        table, acc = mega(table, acc, np.int32(n), datas, lens,
                          issuer_idx, valid)
        int(fetch(acc))
        dt = (time.perf_counter() - t0) / n
        say(f"{'full':7s} {dt * 1e3:9.2f} ms/sweep  "
            f"{dt / batch * 1e9:8.1f} ns/entry  ({n} sweeps)")
        return dt

    def run_preparsed():
        """The walker-free step, timed with the headline methodology:
        host-shaped compact inputs resident on device, the serial
        epoch window restamped per sweep (unique identities, all-fresh
        inserts), one fori_loop execution per chunk, synchronous value
        read. Its rate vs `full` is the pre-parsed lane's device-side
        win (the ISSUE-7 acceptance gate runs exactly this on CPU)."""
        rows = np.asarray(datas[0] if hasattr(datas, "shape") else datas[0])
        rows = np.asarray(rows, np.uint8)
        s = packing.MAX_SERIAL_BYTES
        cols = tpl.serial_off + np.arange(s)
        serials0 = rows[:, cols].copy()
        serials0[:, tpl.serial_len:] = 0
        serials_s = serials0[None]  # [K=1, B, 46]
        slen = np.full((1, batch), tpl.serial_len, np.int32)
        nah = np.full((1, batch), packing.DEFAULT_BASE_HOUR + 1000,
                      np.int32)
        iidx = np.zeros((1, batch), np.int32)
        ins = np.ones((1, batch), bool)

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def mega(table, acc, n_sweeps, serials, slen, nah, iidx, ins):
            def body(sw, carry):
                table, acc = carry
                e = acc + jnp.uint32(sw) + jnp.uint32(1)
                eb = jnp.stack(
                    [(e >> 24) & 0xFF, (e >> 16) & 0xFF, (e >> 8) & 0xFF,
                     e & 0xFF]).astype(jnp.uint8)
                # Epoch window at serial bytes 4..8 (the headline's
                # schema); the lane counter sits in the last 4 bytes.
                sers = serials.at[:, :, 4:8].set(eb[None, None, :])
                table, out = pipeline.preparsed_core(
                    table, sers, slen, nah, iidx, ins,
                    jnp.int32(packing.DEFAULT_BASE_HOUR))
                return table, acc + out.packed[:, 0].sum().astype(jnp.uint32)
            return jax.lax.fori_loop(0, n_sweeps, body, (table, acc))

        fetch = jax.jit(lambda a: a + jnp.uint32(0))
        table = mk_table(cap)
        acc = jax.device_put(np.uint32(0))
        t0 = time.perf_counter()
        table, acc = mega(table, acc, np.int32(1), serials_s, slen, nah,
                          iidx, ins)
        int(fetch(acc))
        say(f"  preparsed: compile+warmup {time.perf_counter() - t0:.1f}s")
        t0 = time.perf_counter()
        table, acc = mega(table, acc, np.int32(1), serials_s, slen, nah,
                          iidx, ins)
        int(fetch(acc))
        per_sweep = max(time.perf_counter() - t0, 1e-4)
        budget = max(1, int(cap * 0.5) // batch - 3)
        n = max(2, min(int(exec_target_s / per_sweep), budget, 200))
        t0 = time.perf_counter()
        table, acc = mega(table, acc, np.int32(n), serials_s, slen, nah,
                          iidx, ins)
        int(fetch(acc))
        dt = (time.perf_counter() - t0) / n
        say(f"{'prepar.':7s} {dt * 1e3:9.2f} ms/sweep  "
            f"{dt / batch * 1e9:8.1f} ns/entry  ({n} sweeps)")
        return dt

    def run_decode():
        """Host decode + sidecar throughput vs intra-chunk threads.

        Pure host work (no device involved): one wire batch decoded at
        each thread count through the native worker pool, best-of-3,
        with BYTE-EXACT parity asserted against threads=1 at every
        point — the scaling number is only meaningful if the parallel
        split is invisible in the outputs."""
        from ct_mapreduce_tpu.native import available as nat_available
        from ct_mapreduce_tpu.native import leafpack

        if not nat_available():
            say("decode  skipped: native library unavailable")
            return None
        n = int(os.environ.get("CT_SC_DECODE_N", str(min(batch, 1 << 16))))
        tpls = [syncerts.make_template(issuer_cn=f"Decode {k}")
                for k in range(2)]
        t0 = time.perf_counter()
        lis, eds = syncerts.make_wire_batch(tpls, 0, n)
        say(f"  decode: wire setup {time.perf_counter() - t0:.1f}s "
            f"({n} entries)")
        cpu = os.cpu_count() or 1
        base = None
        curve = {}
        for t in sorted({1, 2, 4, cpu}):
            best = None
            for _ in range(3):
                t0 = time.perf_counter()
                dec = leafpack.decode_raw_batch(lis, eds, 1024, threads=t)
                sc = leafpack.extract_sidecars(dec.data, dec.length,
                                               threads=t)
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            if base is None:
                base = (dec, sc, best)
            else:
                for fld in ("data", "length", "timestamp_ms",
                            "entry_type", "status", "issuer_group"):
                    assert np.array_equal(
                        getattr(base[0], fld), getattr(dec, fld)), (
                        f"decode threads={t}: {fld} diverged from "
                        "threads=1")
                assert base[0].group_issuers == dec.group_issuers
                for fld in vars(base[1]):
                    assert np.array_equal(
                        getattr(base[1], fld), getattr(sc, fld)), (
                        f"sidecar threads={t}: {fld} diverged from "
                        "threads=1")
            curve[t] = best
            speedup = base[2] / best
            say(f"decode  t={t:<3d} {best * 1e3:9.2f} ms/batch  "
                f"{best / n * 1e9:8.1f} ns/entry  ({speedup:.2f}x vs t=1, "
                "parity exact)")
        return curve

    def run_dispatch():
        """Staged-envelope K-curve: fixed total work (8 chunks of B
        lanes), varying chunks/dispatch. Every dispatch is the REAL
        production shape — host rows → one device_put → one
        ingest_step_staged call — so the K=1 vs K=8 wall delta is
        exactly the per-dispatch Python + H2D + readback toll the
        staging ring amortizes. Byte parity (packed readbacks + final
        table rows) is asserted against K=1 at every point.

        Since round 21 the corpus build and the per-K sweep live in
        tune.harness (shared with the autotuner's staging provider)."""
        from ct_mapreduce_tpu.tune import harness

        b = int(os.environ.get("CT_SC_DISPATCH_B", "1024"))
        n_chunks = 8
        corpus = harness.staged_dispatch_corpus(b=b, n_chunks=n_chunks,
                                                pad_len=pad_len)
        say(f"  dispatch: {n_chunks} chunks x {b} lanes, pad {pad_len}")

        base = None
        for k in (1, 2, 4, 8):
            harness.staged_dispatch_run(corpus, k, mk_table=mk_table)
            best = None
            for _ in range(3):
                dt, packed, rows = harness.staged_dispatch_run(
                    corpus, k, mk_table=mk_table)
                best = dt if best is None else min(best, dt)
            if base is None:
                base = (packed, rows, best)
            else:
                assert np.array_equal(base[0], packed), (
                    f"dispatch K={k}: packed readback diverged from K=1")
                assert np.array_equal(base[1], rows), (
                    f"dispatch K={k}: table rows diverged from K=1")
            per_chunk = best / n_chunks
            say(f"dispatch K={k:<2d} {best * 1e3:9.2f} ms/8chunks  "
                f"{per_chunk * 1e3:8.2f} ms/chunk  "
                f"{per_chunk / b * 1e9:8.1f} ns/entry  "
                f"({base[2] / best:.2f}x vs K=1, parity exact)")

    def run_verify():
        """Device ns/signature: precompute on/off × window-size curve
        at each batch width, plus a P-384 leg — host-parity asserted
        at EVERY (curve, window, width) point.

        Methodology matches the headline: jitted kernel, warmup run
        (compile + table builds excluded), best-of-3 timed runs each
        ending in the synchronous verdict readback. Window > 0 legs
        measure the lane's steady state: G/Q tables device-resident
        before the timed region (100% qtable hits — the production
        regime under <100 log keys). The corpus tiles 64 unique
        signatures under 7 distinct keys (3/4 valid, 1/4 mutated) so
        host-side generation stays cheap at B=4096.

        Since round 21 the corpus build and the per-point measurement
        (tables, warmup, best-of-3, host parity every run) live in
        tune.harness — shared with the autotuner's verify provider.

        Env: CT_SC_VERIFY_B (widths, default 256,1024,4096),
        CT_SC_VERIFY_W (windows, default 0,2,4,8; 0 = legacy ladder),
        CT_SC_VERIFY_P384_B (P-384 widths, default 256; empty
        disables), CT_SC_VERIFY_P384_W (default 0,8)."""
        from ct_mapreduce_tpu.ops import ecdsa
        from ct_mapreduce_tpu.tune import harness

        def sweep(ops, widths, windows, n_uniq=64, n_keys=7):
            corpus = harness.verify_corpus(ops, n_uniq, n_keys)
            for w in widths:
                base_ns = None
                for win in windows:
                    tr = harness.verify_point(ops, w, win, corpus,
                                              reps=3)
                    say(f"  verify {ops.name} B={w} w={win}: "
                        f"compile+warmup {tr.compile_s:.1f}s")
                    ns = tr.best / w * 1e9
                    if base_ns is None:
                        base_ns = ns
                    say(f"verify  {ops.name} B={w:<5d} w={win:<2d} "
                        f"{tr.best * 1e3:9.2f} ms/batch  "
                        f"{ns:12.1f} ns/sig"
                        f"  ({base_ns / ns:.2f}x vs w={windows[0]}, "
                        f"parity exact)")

        widths = [int(w) for w in os.environ.get(
            "CT_SC_VERIFY_B", "256,1024,4096").split(",") if w]
        windows = [int(w) for w in os.environ.get(
            "CT_SC_VERIFY_W", "0,2,4,8").split(",") if w != ""]
        sweep(ecdsa.P256_OPS, widths, windows)
        p384_b = [int(w) for w in os.environ.get(
            "CT_SC_VERIFY_P384_B", "256").split(",") if w]
        p384_w = [int(w) for w in os.environ.get(
            "CT_SC_VERIFY_P384_W", "0,8").split(",") if w != ""]
        if p384_b:
            sweep(ecdsa.P384_OPS, p384_b, p384_w, n_uniq=16, n_keys=3)

    def run_ckpt():
        """Checkpoint-plane cost (CTMRCK02, round 22): full ck01 save
        wall vs incremental ck02 tick wall at churn ∈ {0.1%, 1%, 10%}
        of the fixture, plus restore wall at several chain depths —
        restored-state parity (tune.harness.ckpt_state_digest)
        asserted at every point, against both the live writer and a
        ck01 oracle save of the same state.

        The fixture pre-fills via the bulk path (setup, untimed);
        churn folds through the pre-parsed lane so the per-tick dirty
        log records it exactly as production folds would.

        Env: CT_SC_CKPT_ENTRIES (default 10**7), CT_SC_CKPT_BITS
        (table log2 capacity, default 25), CT_SC_CKPT_CHURN (default
        0.001,0.01,0.1), CT_SC_CKPT_DEPTHS (restore chain depths,
        default 1,4,8). CT_SC_CKPT_STATE names a reusable fixture
        checkpoint: the 10^7 pre-fill (host-side SHA-256 of every
        serial) dwarfs the measured section, so build it once, save
        it as a plain ck01 snapshot, and let later invocations
        restore instead of rebuild (same-topology restores load rows
        directly — no rehash)."""
        import shutil
        import tempfile

        from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
        from ct_mapreduce_tpu.tune import harness

        entries = int(os.environ.get("CT_SC_CKPT_ENTRIES", str(10**7)))
        bits = int(os.environ.get("CT_SC_CKPT_BITS", "25"))
        churns = [float(c) for c in os.environ.get(
            "CT_SC_CKPT_CHURN", "0.001,0.01,0.1").split(",") if c]
        depths = [int(x) for x in os.environ.get(
            "CT_SC_CKPT_DEPTHS", "1,4,8").split(",") if x]

        t0 = time.perf_counter()
        state = os.environ.get("CT_SC_CKPT_STATE", "")
        if state and os.path.exists(state):
            say(f"ckpt: restoring {entries:,}-entry fixture from {state}")
            agg = TpuAggregator(capacity=1 << bits, batch_size=4096,
                                grow_at=0.0)
            agg.load_checkpoint(state)
            eh = agg.base_hour + 1000
            if int(agg._table_fill) != entries:
                raise SystemExit(
                    f"fixture state {state} holds {int(agg._table_fill):,}"
                    f" entries, wanted {entries:,}: rebuild it")
            say(f"ckpt: fixture restored in {time.perf_counter() - t0:.1f}s")
        else:
            say(f"ckpt: building {entries:,}-entry fixture (2^{bits} slots)")
            agg, eh = harness.build_aggregator(entries, bits)
            say(f"ckpt: fixture built in {time.perf_counter() - t0:.1f}s")
            if state:
                agg.configure_checkpointing(mode="ck01")
                agg.save_checkpoint(state)
                say(f"ckpt: fixture cached to {state}")
        tmp = tempfile.mkdtemp(prefix="stagecost-ckpt.")
        try:
            def fresh_reader():
                return TpuAggregator(capacity=1 << bits,
                                     batch_size=4096, grow_at=0.0)

            p01 = os.path.join(tmp, "ck01.npz")
            agg.configure_checkpointing(mode="ck01")
            t0 = time.perf_counter()
            agg.save_checkpoint(p01)
            full_s = time.perf_counter() - t0
            say(f"ckpt  ck01 full save   {full_s * 1e3:10.1f} ms  "
                f"({os.path.getsize(p01) / 1e6:.1f} MB)")

            p02 = os.path.join(tmp, "ck02.npz")
            agg.configure_checkpointing(mode="ck02",
                                        max_chain=len(churns) + 1)
            t0 = time.perf_counter()
            agg.save_checkpoint(p02)
            say(f"ckpt  ck02 base anchor {(time.perf_counter() - t0) * 1e3:10.1f} ms")

            start = entries
            speedups = {}
            for c in churns:
                nch = max(1, int(entries * c))
                harness.ckpt_churn(agg, eh, nch, start)
                start += nch
                t0 = time.perf_counter()
                agg.save_checkpoint(p02)
                seg_s = time.perf_counter() - t0
                speedups[c] = full_s / seg_s
                seq = agg._ckpt_chain_len
                seg_mb = os.path.getsize(
                    os.path.join(tmp, f"ck02.npz.ckseg-{seq:08d}")) / 1e6
                say(f"ckpt  ck02 tick churn={c:7.2%} ({nch:>9,} rows) "
                    f"{seg_s * 1e3:10.1f} ms  ({seg_mb:.1f} MB, "
                    f"{full_s / seg_s:.1f}x vs full)")

            # Parity at the tip: chain restore == live writer == a
            # ck01 oracle save of the same state.
            want = harness.ckpt_state_digest(agg)
            r = fresh_reader()
            t0 = time.perf_counter()
            r.load_checkpoint(p02)
            say(f"ckpt  ck02 restore (chain {len(churns)})"
                f" {(time.perf_counter() - t0) * 1e3:10.1f} ms")
            harness.require(harness.ckpt_state_digest(r) == want,
                            "ck02 chain restore diverged from writer")
            p01b = os.path.join(tmp, "oracle.npz")
            agg.configure_checkpointing(mode="ck01")
            agg.save_checkpoint(p01b)
            o = fresh_reader()
            o.load_checkpoint(p01b)
            harness.require(harness.ckpt_state_digest(o) == want,
                            "ck01 oracle restore diverged from writer")
            say("ckpt  restore parity exact (ck02 chain == ck01 oracle)")

            # Restore wall vs chain depth (1% churn per tick).
            agg.configure_checkpointing(mode="ck02",
                                        max_chain=max(depths) + 1)
            pd = os.path.join(tmp, "depth.npz")
            agg.save_checkpoint(pd)
            nch = max(1, int(entries * 0.01))
            done = 0
            for d in sorted(depths):
                while done < d:
                    harness.ckpt_churn(agg, eh, nch, start)
                    start += nch
                    agg.save_checkpoint(pd)
                    done += 1
                r = fresh_reader()
                t0 = time.perf_counter()
                r.load_checkpoint(pd)
                w = time.perf_counter() - t0
                harness.require(
                    harness.ckpt_state_digest(r)
                    == harness.ckpt_state_digest(agg),
                    f"restore parity broke at chain depth {d}")
                say(f"ckpt  restore depth={d}  {w * 1e3:10.1f} ms "
                    "(parity exact)")

            one_pct = speedups.get(0.01)
            if one_pct is not None:
                say(f"ckpt  headline: 1%-churn tick {one_pct:.1f}x "
                    "faster than full ck01 save")
        finally:
            shutil.rmtree(tmp, ignore_errors=True)

    stages = [
        ("read", s_read), ("pack", s_pack), ("pack2", s_pack2),
        ("parse", s_parse),
        ("serial", s_serial), ("sha", s_sha), ("lanes", s_lanes),
    ]
    results = {}
    if not only or "ckpt" in only:
        run_ckpt()
    if only == {"ckpt"}:
        return
    if not only or "decode" in only:
        run_decode()
    if only == {"decode"}:
        return
    if not only or "dispatch" in only:
        run_dispatch()
    if only == {"dispatch"}:
        return
    if not only or "verify" in only:
        run_verify()
    if only == {"verify"}:
        return
    for name, fn in stages:
        if only and name not in only:
            continue
        results[name] = run_stage(name, fn)
    if not only or "full" in only:
        results["full"] = run_full()
    if not only or "preparsed" in only:
        results["preparsed"] = run_preparsed()

    order = [n for n, _ in stages] + ["full"]
    got = [n for n in order if n in results]
    say("")
    say("stage deltas (cost of each added phase):")
    prev = 0.0
    for n in got:
        d = results[n] - prev
        say(f"  +{n:7s} {d * 1e3:9.2f} ms  {d / batch * 1e9:8.1f} ns/entry")
        prev = results[n]
    if "preparsed" in results and "full" in results:
        f, pp = results["full"], results["preparsed"]
        say("")
        say(f"preparsed step vs walker step: {pp / batch * 1e9:.1f} vs "
            f"{f / batch * 1e9:.1f} ns/entry "
            f"({'WIN' if pp < f else 'LOSS'}, {f / max(pp, 1e-12):.2f}x)")


if __name__ == "__main__":
    main()
