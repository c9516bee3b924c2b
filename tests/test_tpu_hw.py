"""Real-hardware tier (CT_TPU_TESTS=1): the fused step on the chip.

The reference gates its integration tier on a reachable Redis
(rediscache_test.go:16-28); the analog here is a reachable TPU. Keep
this tier tiny — one compile, a few seconds of chip time — it exists
to prove the shipping step (device build -> parse -> filter ->
fingerprint -> dedup insert -> counts) runs end to end on real
hardware with exact results, not to benchmark it (benchmark/ does that).
"""

import numpy as np
import pytest

from tests.conftest import on_tpu, requires_tpu


@requires_tpu
@pytest.mark.timeout(300)
def test_fused_step_on_hardware():
    import jax
    import jax.numpy as jnp

    from ct_mapreduce_tpu.core import packing
    from ct_mapreduce_tpu.ops import buckettable, pipeline
    from ct_mapreduce_tpu.utils import syncerts

    assert on_tpu(), "CT_TPU_TESTS=1 requires a TPU backend"
    batch, pad_len = 4096, 1024
    tpl = syncerts.make_template()
    datas, lens = syncerts.build_device_batches(tpl, 1, batch, pad_len)
    issuer_idx = jnp.zeros((batch,), jnp.int32)
    valid = jnp.ones((batch,), bool)

    step = jax.jit(pipeline.ingest_core, donate_argnums=(0,),
                   static_argnames=("num_issuers", "max_probes"))
    table = buckettable.make_table(1 << 14)
    table, out = step(
        table, datas[0], lens[0], issuer_idx, valid,
        jnp.int32(500_000), jnp.int32(packing.DEFAULT_BASE_HOUR),
        jnp.zeros((0, 32), jnp.uint8), jnp.zeros((0, 2), jnp.int32),
    )
    wu = np.asarray(out.was_unknown)
    assert wu.sum() == batch  # every lane unique → all fresh inserts
    assert not np.asarray(out.host_lane).any()
    assert int(np.asarray(table.count)) == batch

    # Replay: nothing is fresh the second time (Redis SADD semantics).
    table, out2 = step(
        table, datas[0], lens[0], issuer_idx, valid,
        jnp.int32(500_000), jnp.int32(packing.DEFAULT_BASE_HOUR),
        jnp.zeros((0, 32), jnp.uint8), jnp.zeros((0, 2), jnp.int32),
    )
    assert int(np.asarray(out2.was_unknown).sum()) == 0
    assert int(np.asarray(table.count)) == batch


@requires_tpu
@pytest.mark.timeout(300)
def test_pallas_vs_xla_sha256_equality_on_device():
    """The Pallas fingerprint kernel and the XLA scan must agree
    bit-for-bit ON THE CHIP (CI covers interpret mode only), and both
    must match hashlib ground truth."""
    import hashlib

    import jax.numpy as jnp

    from ct_mapreduce_tpu.ops import pallas_sha256, sha256

    assert on_tpu()
    rng = np.random.default_rng(42)
    msgs = [rng.bytes(int(n)) for n in rng.integers(1, 56, size=512)]
    blocks = np.stack([sha256.pad_message_np(m, 1)[0] for m in msgs])

    xla = np.asarray(sha256.sha256_single_block(jnp.asarray(blocks)))
    pal = np.asarray(
        pallas_sha256.sha256_single_block_pallas(jnp.asarray(blocks))
    )
    np.testing.assert_array_equal(pal, xla)
    for i in (0, 1, 255, 511):
        want = hashlib.sha256(msgs[i]).digest()
        got = b"".join(int(w).to_bytes(4, "big") for w in xla[i])
        assert got == want


@requires_tpu
@pytest.mark.timeout(480)
def test_fused_step_parity_at_production_width():
    """One step at the production batch width (131,072 lanes — the
    width behind the recorded 1.31M entries/s): exact all-fresh parity,
    nothing spilled."""
    import jax
    import jax.numpy as jnp

    from ct_mapreduce_tpu.core import packing
    from ct_mapreduce_tpu.ops import buckettable, pipeline
    from ct_mapreduce_tpu.utils import syncerts

    assert on_tpu()
    batch, pad_len = 131_072, 1024
    tpl = syncerts.make_template()
    datas, lens = syncerts.build_device_batches(tpl, 1, batch, pad_len)
    issuer_idx = jnp.zeros((batch,), jnp.int32)
    valid = jnp.ones((batch,), bool)

    step = jax.jit(pipeline.ingest_core, donate_argnums=(0,),
                   static_argnames=("num_issuers", "max_probes"))
    table = buckettable.make_table(1 << 20)
    table, out = step(
        table, datas[0], lens[0], issuer_idx, valid,
        jnp.int32(500_000), jnp.int32(packing.DEFAULT_BASE_HOUR),
        jnp.zeros((0, 32), jnp.uint8), jnp.zeros((0, 2), jnp.int32),
    )
    assert int(np.asarray(out.was_unknown).sum()) == batch
    assert not np.asarray(out.host_lane).any()
    assert int(np.asarray(table.count)) == batch


@requires_tpu
@pytest.mark.timeout(300)
def test_sharded_step_on_chip_mesh():
    """The mesh-sharded path (shard_map + all_to_all + psum) compiles
    and runs on the real backend — a 1-chip mesh here; the 8-way
    virtual mesh runs in CI and the driver's dryrun."""
    import jax
    from jax.sharding import Mesh

    from ct_mapreduce_tpu.agg import sharded
    from ct_mapreduce_tpu.utils import syncerts

    assert on_tpu()
    mesh = Mesh(np.asarray(jax.devices()[:1]), (sharded.AXIS,))
    batch, pad_len = 1024, 1024
    tpl = syncerts.make_template()
    data, length = syncerts.stamp_batch_array(tpl, start=0, batch=batch,
                                              pad_len=pad_len)
    dedup = sharded.ShardedDedup(mesh, capacity=1 << 14)
    out = dedup.step(data, length, np.zeros((batch,), np.int32),
                     np.ones((batch,), bool), now_hour=500_000)
    fresh = int(np.asarray(out.was_unknown).sum())
    host = int(np.asarray(out.host_lane).sum())
    assert fresh + host == batch
    assert fresh > 0
    out2 = dedup.step(data, length, np.zeros((batch,), np.int32),
                      np.ones((batch,), bool), now_hour=500_000)
    assert int(np.asarray(out2.was_unknown).sum()) == 0


@requires_tpu
@pytest.mark.timeout(480)
def test_e2e_ingest_leg_small_on_hardware():
    """Wire format → decode → pack → H2D → device step → drain, on the
    chip, at small scale: the production AggregatorSink path with exact
    totals and per-issuer attribution (the shape the benchmark
    measures at full size)."""
    import base64

    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
    from ct_mapreduce_tpu.ingest import leaf as leaflib
    from ct_mapreduce_tpu.ingest.sync import AggregatorSink, RawBatch
    from ct_mapreduce_tpu.utils import syncerts

    assert on_tpu()
    batch = 2048
    tpls = [syncerts.make_template(issuer_cn=f"HW Issuer {k}")
            for k in range(2)]
    eds = [base64.b64encode(
        leaflib.encode_extra_data([t.issuer_der])).decode() for t in tpls]
    lis, ed_col = [], []
    for j in range(batch):
        k = j & 1
        der = syncerts.stamp_serial(tpls[k], j)
        lis.append(base64.b64encode(
            leaflib.encode_leaf_input(der, 1_700_000_000_000 + j)).decode())
        ed_col.append(eds[k])

    agg = TpuAggregator(capacity=1 << 14, batch_size=batch)
    sink = AggregatorSink(agg, flush_size=batch, device_queue_depth=1)
    sink.store_raw_batch(RawBatch(lis, ed_col, 0, "hw-log"))
    sink.flush()
    snap = agg.drain()
    assert snap.total == batch
    by_issuer = {}
    for (iss, _exp), c in snap.counts.items():
        by_issuer[iss] = by_issuer.get(iss, 0) + c
    assert sorted(by_issuer.values()) == [batch // 2, batch // 2]
