"""Pre-parsed ingest lane over the mesh: host-routed sharded step.

``ShardedAggregator._device_step_preparsed`` routes every sidecar lane
to its fingerprint's home shard ON THE HOST (numpy SHA-256 mirror +
the `_shard_of` hash), partitions lanes per shard before H2D, and runs
a shard-local fingerprint+insert step — no ``all_to_all``. Contracts
pinned here:

1. The numpy fingerprint mirror equals the scalar host reference (and
   therefore the device SHA) word for word.
2. mesh=1 sharded-preparsed is parity-EXACT with single-chip preparsed:
   was-unknown lanes, metrics, drains — including probe-overflow spill
   counts through the compacted-flag readback and its bitmask
   fallback.
3. A multi-shard mesh keeps the same aggregate parity, dedups across
   replays, and psum's per-issuer counts correctly.
4. The old loud rejection is gone: preparsedIngest + meshShape is a
   supported combination end to end through the sink.
"""

import datetime
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
from jax.sharding import Mesh

from ct_mapreduce_tpu.core import packing
from ct_mapreduce_tpu.native import available, leafpack

pytestmark = pytest.mark.skipif(
    not available(), reason="native library unavailable (no C++ compiler)")

UTC = datetime.timezone.utc


def _mesh(n):
    devs = np.array(jax.devices()[:n])
    assert devs.size == n, "conftest must provide 8 virtual devices"
    return Mesh(devs, ("shard",))


def _fixtures(n, pad=1024):
    from __graft_entry__ import _NOW, _packed_batch

    data, length, issuer_idx, valid, templates = _packed_batch(n, pad)
    sc = leafpack.extract_sidecars(data, length)
    return data, length, issuer_idx, valid, templates, sc, _NOW


def test_fingerprints_np_matches_host_reference():
    rng = np.random.default_rng(3)
    n = 128
    ii = rng.integers(0, packing.MAX_ISSUERS, n).astype(np.int32)
    eh = rng.integers(400_000, 650_000, n).astype(np.int32)
    slen = rng.integers(1, packing.MAX_SERIAL_BYTES + 1, n).astype(np.int32)
    ser = np.zeros((n, packing.MAX_SERIAL_BYTES), np.uint8)
    for i in range(n):
        ser[i, : slen[i]] = rng.integers(0, 256, slen[i])
    fps = packing.fingerprints_np(ii, eh, ser, slen)
    for i in range(n):
        want = packing.fingerprint_host(
            int(ii[i]), int(eh[i]), bytes(ser[i, : slen[i]]))
        assert tuple(int(x) for x in fps[i]) == want, i


def _run_preparsed(agg, fixtures, repeats=1):
    data, length, issuer_idx, valid, templates, sc, _now = fixtures
    for t in templates:
        agg.registry.get_or_assign(t.issuer_der)
    results = [agg.ingest_preparsed(sc, issuer_idx, valid, data, length)
               for _ in range(repeats)]
    return results, agg


def test_mesh1_parity_exact_with_single_chip():
    """counts, spill counts, flagged-lane ids: mesh=1 must be
    indistinguishable from the single-chip pre-parsed lane (same table
    structure at matched capacity, same lane processing order)."""
    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
    from ct_mapreduce_tpu.agg.sharded_agg import ShardedAggregator

    fx = _fixtures(96)
    now = fx[6]
    (r1a, r2a), a = _run_preparsed(
        TpuAggregator(capacity=1 << 12, batch_size=32, now=now),
        fx, repeats=2)
    (r1b, r2b), b = _run_preparsed(
        ShardedAggregator(_mesh(1), capacity=1 << 12, batch_size=32,
                          now=now),
        fx, repeats=2)
    np.testing.assert_array_equal(r1a.was_unknown, r1b.was_unknown)
    np.testing.assert_array_equal(r2a.was_unknown, r2b.was_unknown)
    np.testing.assert_array_equal(r1a.filtered, r1b.filtered)
    assert r1a.serials == r1b.serials
    assert a.metrics == b.metrics, (a.metrics, b.metrics)
    assert a.drain().counts == b.drain().counts


def test_mesh1_overflow_spill_parity_exact():
    """Probe-overflow spills (tiny table, single probe) must surface
    through the per-shard compacted-flag readback — including the
    full-bitmask fallback past flag_cap — at EXACTLY the lanes the
    single-chip lane flags. Capacity 48 rounds identically under both
    table constructions (bucket layout)."""
    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
    from ct_mapreduce_tpu.agg.sharded_agg import ShardedAggregator

    fx = _fixtures(512)
    now = fx[6]
    (r1,), a = _run_preparsed(
        TpuAggregator(capacity=48, batch_size=512, now=now, max_probes=1,
                      grow_at=0, max_capacity=48), fx)
    (r2,), b = _run_preparsed(
        ShardedAggregator(_mesh(1), capacity=48, batch_size=512, now=now,
                          max_probes=1, grow_at=0, max_capacity=48), fx)
    assert a.capacity == b.capacity == 48
    assert a.metrics["overflow"] > 64  # past flag_cap ⇒ spill fallback
    assert a.metrics == b.metrics, (a.metrics, b.metrics)
    np.testing.assert_array_equal(r1.was_unknown, r2.was_unknown)
    assert a.drain().counts == b.drain().counts


def test_mesh8_parity_dedup_and_issuer_counts():
    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
    from ct_mapreduce_tpu.agg.sharded_agg import ShardedAggregator

    fx = _fixtures(96)
    now = fx[6]
    (r1a, r2a), a = _run_preparsed(
        TpuAggregator(capacity=1 << 12, batch_size=32, now=now),
        fx, repeats=2)
    (r1b, r2b), b = _run_preparsed(
        ShardedAggregator(_mesh(8), capacity=1 << 12, batch_size=32,
                          now=now),
        fx, repeats=2)
    # First pass inserts everything, replay inserts nothing — and the
    # psum'd per-issuer totals match the single-chip fold exactly.
    assert r1b.was_unknown.all() and not r2b.was_unknown.any()
    np.testing.assert_array_equal(r1a.was_unknown, r1b.was_unknown)
    assert a.metrics == b.metrics, (a.metrics, b.metrics)
    np.testing.assert_array_equal(a.issuer_totals, b.issuer_totals)
    assert a.drain().counts == b.drain().counts
    assert b._table_fill_exact() == 96


def test_routing_is_fingerprint_home_shard():
    """The host route must place every lane on the shard the device
    hash would pick (shard_of_np == _shard_of on the same words)."""
    import jax.numpy as jnp

    from ct_mapreduce_tpu.agg import sharded

    rng = np.random.default_rng(11)
    fps = rng.integers(0, 2**32, size=(257, 4), dtype=np.uint64).astype(
        np.uint32)
    for n_shards in (2, 8):
        host = sharded.shard_of_np(fps, n_shards)
        dev = np.asarray(sharded._shard_of(jnp.asarray(fps), n_shards))
        np.testing.assert_array_equal(host, dev)


def test_sink_accepts_preparsed_with_mesh():
    """End to end through AggregatorSink: preparsedIngest + mesh is a
    supported combination (the round-7 rejection is gone), undecidable
    lanes still replay through the walker path on the mesh."""
    import base64 as b64mod

    from ct_mapreduce_tpu.agg.sharded_agg import ShardedAggregator
    from ct_mapreduce_tpu.ingest import leaf as leaflib
    from ct_mapreduce_tpu.ingest.sync import AggregatorSink, RawBatch
    from ct_mapreduce_tpu.ops import der_kernel
    from tests import certgen

    FUTURE = datetime.datetime(2031, 6, 15, tzinfo=UTC)
    issuer = certgen.make_cert(serial=1, issuer_cn="Mesh CA", is_ca=True,
                               not_after=FUTURE)
    pairs = [(certgen.make_cert(serial=100 + s, issuer_cn="Mesh CA",
                                is_ca=False, not_after=FUTURE), issuer)
             for s in range(8)]
    # One walker-undecidable cert (over the extension scan budget):
    # must replay through the sharded walker path, not get lost.
    pairs.append((certgen.make_cert(
        serial=200, issuer_cn="Mesh CA", is_ca=False, not_after=FUTURE,
        extra_extensions=der_kernel.MAX_EXTS + 4), issuer))
    lis, eds = [], []
    for j, (leaf, iss) in enumerate(pairs):
        lis.append(b64mod.b64encode(leaflib.encode_leaf_input(
            leaf, timestamp_ms=1700000000000 + j)).decode())
        eds.append(b64mod.b64encode(
            leaflib.encode_extra_data([iss])).decode())

    agg = ShardedAggregator(_mesh(8), capacity=1 << 12, batch_size=16)
    sink = AggregatorSink(agg, flush_size=16, device_queue_depth=0,
                          preparsed=True)
    sink.store_raw_batch(RawBatch(lis, eds, 0, "mesh-log"))
    sink.flush()
    snap = agg.drain()
    assert snap.total == len(pairs)
    assert agg.metrics["inserted"] == len(pairs)
    # The undecidable lane took the exact host lane via walker replay.
    assert agg.metrics["host_lane"] == 1


@pytest.mark.parametrize("shards", [0, 8], ids=["one-chip", "mesh8"])
def test_clean_stream_reads_back_only_the_compact_flag_block(shards):
    """With no lane flagged, what comes back from the device a chunk is
    the fixed block (two count words and the compacted ids, at most
    ``flag_cap`` of them), never a status word a lane: the same budget
    on one chip and reassembled from a mesh's shards."""
    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
    from ct_mapreduce_tpu.agg.sharded_agg import ShardedAggregator
    from ct_mapreduce_tpu.telemetry import metrics as tmetrics

    n, chunk = 512, 256
    fx = _fixtures(n)
    now = fx[6]
    agg = (ShardedAggregator(_mesh(shards), capacity=1 << 12,
                             batch_size=chunk, now=now) if shards
           else TpuAggregator(capacity=1 << 12, batch_size=chunk, now=now))
    sink = tmetrics.InMemSink()
    prev = tmetrics.get_sink()
    tmetrics.set_sink(sink)
    try:
        (res,), agg = _run_preparsed(agg, fx)
    finally:
        tmetrics.set_sink(prev)
    assert res.was_unknown.all() and agg.metrics["overflow"] == 0
    flag_cap = min(1024, max(64, chunk // 64), chunk)
    got = sink.snapshot()["counters"]["ingest.d2h_flag_bytes"]
    assert 0 < got <= 4 * (2 + flag_cap) * (n // chunk), got
    assert got < 4 * n
