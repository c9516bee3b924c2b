"""Membership-oracle parity fuzz (ISSUE 5 satellite): the three
``contains`` surfaces the query plane can route through —
``hashtable.contains_np``, ``buckettable.contains_np``, and the
jitted device ``contains`` of each layout — must agree lane for lane
on a shared corpus of present, absent, and just-evicted keys.

"Just-evicted" pins the rebuild hazard: keys that lived in an earlier
epoch of the table (drained away by a rebuild that reinserted only a
subset — exactly what grow-and-rehash does) must read absent
everywhere, not linger as stale positives in any one probe
implementation."""

import numpy as np
import pytest

from ct_mapreduce_tpu.ops import buckettable, hashtable


def _corpus(seed: int, n: int):
    """Random fingerprint rows split into kept / evicted / absent."""
    rng = np.random.default_rng(seed)
    keys = rng.integers(0, 2**32, size=(3 * n, 4), dtype=np.uint32)
    # Distinct rows (collisions would blur the class boundaries).
    _, first = np.unique(
        keys.view([("", np.uint32)] * 4), return_index=True)
    keys = keys[np.sort(first)]
    n = len(keys) // 3
    return keys[:n], keys[n : 2 * n], keys[2 * n : 3 * n]


def _build_open(kept, evicted, max_probes):
    """Open-addressed table holding exactly ``kept``: insert
    kept+evicted, then rebuild (fresh epoch) with only kept — the
    grow-and-rehash shape."""
    import jax.numpy as jnp

    cap = 1 << (int(len(kept) * 4).bit_length())
    meta = jnp.arange(len(kept) + len(evicted), dtype=jnp.uint32) + 1
    both = jnp.asarray(np.concatenate([kept, evicted]))
    valid = jnp.ones((both.shape[0],), bool)
    state = hashtable.make_table(cap)
    state, wu, ovf = hashtable.insert(state, both, meta, valid,
                                      max_probes=max_probes)
    assert not bool(np.asarray(ovf).any()), "corpus overflowed; raise cap"
    state2 = hashtable.make_table(cap)
    state2, wu, ovf = hashtable.insert(
        state2, jnp.asarray(kept), meta[: len(kept)],
        valid[: len(kept)], max_probes=max_probes)
    assert bool(np.asarray(wu).all()) and not bool(np.asarray(ovf).any())
    return state2


def _build_bucket(kept, evicted, max_probes):
    import jax.numpy as jnp

    cap = int(len(kept) * 4)
    meta = jnp.arange(len(kept) + len(evicted), dtype=jnp.uint32) + 1
    both = jnp.asarray(np.concatenate([kept, evicted]))
    valid = jnp.ones((both.shape[0],), bool)
    state = buckettable.make_table(cap)
    state, wu, ovf = buckettable.insert(state, both, meta, valid,
                                        max_probes=max_probes)
    assert not bool(np.asarray(ovf).any()), "corpus overflowed; raise cap"
    state2 = buckettable.make_table(cap)
    state2, wu, ovf = buckettable.insert(
        state2, jnp.asarray(kept), meta[: len(kept)],
        valid[: len(kept)], max_probes=max_probes)
    assert bool(np.asarray(wu).all()) and not bool(np.asarray(ovf).any())
    return state2


# Seeds 17/91 are @slow since round 15 (tier-1 budget banking, ISSUE
# 10): three seeds of one fuzz sweep walk the same layout/device code
# paths — seed 3 keeps the tier-1 gate, the redundant re-rolls run in
# the full (unmarked) suite.
@pytest.mark.parametrize("seed", [
    3,
    pytest.param(17, marks=pytest.mark.slow),
    pytest.param(91, marks=pytest.mark.slow),
])
def test_contains_parity_open_vs_bucket_vs_device(seed):
    max_probes = 32
    kept, evicted, absent = _corpus(seed, 512)
    probe = np.concatenate([kept, evicted, absent])
    want = np.concatenate([
        np.ones((len(kept),), bool),
        np.zeros((len(evicted) + len(absent),), bool),
    ])

    open_state = _build_open(kept, evicted, max_probes)
    bucket_state = _build_bucket(kept, evicted, max_probes)

    import jax.numpy as jnp

    results = {
        "hashtable.contains_np": hashtable.contains_np(
            np.asarray(open_state.rows), probe, max_probes=max_probes),
        "hashtable.contains": np.asarray(hashtable.contains(
            open_state, jnp.asarray(probe), max_probes=max_probes)),
        "buckettable.contains_np": buckettable.contains_np(
            np.asarray(bucket_state.rows), probe, max_probes=max_probes),
        "buckettable.contains": np.asarray(buckettable.contains(
            bucket_state, jnp.asarray(probe), max_probes=max_probes)),
    }
    for name, got in results.items():
        miss = np.nonzero(got != want)[0]
        assert miss.size == 0, (
            f"{name} disagrees with ground truth on {miss.size} lanes "
            f"(first at {miss[:5]}; lane classes: kept<{len(kept)}, "
            f"evicted<{len(kept) + len(evicted)}, then absent)")


def test_contains_parity_within_batch_duplicates():
    """Duplicate probe lanes (the batcher coalesces independent
    requests, so the same key can appear many times in one contains
    batch) answer identically on every surface."""
    max_probes = 32
    kept, evicted, absent = _corpus(23, 128)
    open_state = _build_open(kept, evicted, max_probes)
    bucket_state = _build_bucket(kept, evicted, max_probes)
    rng = np.random.default_rng(5)
    pool = np.concatenate([kept, evicted, absent])
    pick = rng.integers(0, len(pool), size=1024)
    probe = pool[pick]
    want = pick < len(kept)

    import jax.numpy as jnp

    for name, got in (
        ("open np", hashtable.contains_np(
            np.asarray(open_state.rows), probe, max_probes=max_probes)),
        ("open dev", np.asarray(hashtable.contains(
            open_state, jnp.asarray(probe), max_probes=max_probes))),
        ("bucket np", buckettable.contains_np(
            np.asarray(bucket_state.rows), probe, max_probes=max_probes)),
        ("bucket dev", np.asarray(buckettable.contains(
            bucket_state, jnp.asarray(probe), max_probes=max_probes))),
    ):
        assert np.array_equal(got, want), f"{name} diverged"


def test_contains_parity_sharded_view():
    """The sharded contains (device: routed on the host, every shard
    probing its own block under ``shard_map``) vs the query plane's
    routed host probe (shard_of_np + per-block contains_np) on the
    same sharded rows."""
    import jax
    import jax.numpy as jnp

    from ct_mapreduce_tpu.agg import sharded

    max_probes = 32
    n_shards = len(jax.devices())
    kept, evicted, absent = _corpus(41, 256)
    # Build per-shard open tables by routing, then concatenate blocks —
    # the layout ShardedDedup's row array has.
    cap_loc = 1 << int((len(kept) * 4 // n_shards).bit_length())
    blocks = []
    dest = sharded.shard_of_np(kept, n_shards)
    for s in range(n_shards):
        state = hashtable.make_table(cap_loc)
        sel = kept[dest == s]
        if len(sel):
            state, wu, ovf = hashtable.insert(
                state, jnp.asarray(sel),
                jnp.arange(len(sel), dtype=jnp.uint32) + 1,
                jnp.ones((len(sel),), bool), max_probes=max_probes)
            assert not bool(np.asarray(ovf).any())
        blocks.append(np.asarray(state.rows))
    rows = np.concatenate(blocks)

    probe = np.concatenate([kept, evicted, absent])
    want = np.concatenate([
        np.ones((len(kept),), bool),
        np.zeros((len(evicted) + len(absent),), bool),
    ])
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()), (sharded.AXIS,))
    keys, dest_k, pos = sharded.route_to_shards(probe, n_shards)
    assert keys.shape == (n_shards, sharded.probe_width(len(probe)), 4)
    dev = sharded.shard_contains(
        jax.device_put(rows, NamedSharding(mesh, PartitionSpec(sharded.AXIS))),
        keys, "open", max_probes)[dest_k, pos]
    # The routed host probe, as the query plane's sharded view runs it.
    dest_p = sharded.shard_of_np(probe, n_shards)
    host = np.zeros((len(probe),), bool)
    for s in np.unique(dest_p):
        sel = dest_p == s
        host[sel] = hashtable.contains_np(
            rows[s * cap_loc : (s + 1) * cap_loc], probe[sel],
            max_probes=max_probes)
    assert np.array_equal(dev, want)
    assert np.array_equal(host, want)
