"""Device-resident dedup set: open-addressed hash table in HBM.

NOTE: since round 4 this slot-granular layout is the FALLBACK
(``CTMR_TABLE=open``); the default is the bucketized table in
:mod:`ct_mapreduce_tpu.ops.buckettable`, whose measured insert is
~10x cheaper on v5e (709 vs ~68 ns/entry at 2^20 lanes — this
module's per-round 5-word row scatter alone prices at 86.5 ns/lane
from tile-misalignment; July installation's random-access probe,
git history).
Kept for layout comparisons and pre-round-4 checkpoint compatibility.

This is the TPU-native replacement for the reference's per-certificate
Redis ``SADD`` round trip (`WasUnknown`,
/root/reference/storage/knowncertificates.go:38-55 →
/root/reference/storage/rediscache.go:57-65): a whole batch of
certificate fingerprints is inserted in one jitted op, returning the
per-lane "was unknown" bit with the same semantics Redis set-insert
gives (first writer wins; re-inserting a known key is a no-op).

Keys are 128-bit truncated SHA-256 fingerprints of
``(expHour, issuerDigest, serial)`` — see
:func:`ct_mapreduce_tpu.core.packing.fingerprint_block` — stored as
``uint32[capacity, 4]``. The all-zero key is the empty sentinel; real
fingerprints are remapped away from it (probability 2^-128 anyway).

Insertion algorithm (bounded trip count, jit/pjit-friendly — the probe
loop is a ``lax.while_loop`` that exits as soon as no lane is pending;
sort-free, gather-light):

Each lane carries its own probe index ``r`` (triangular probing over a
power-of-two capacity, guaranteed full-cycle). Per round, every
pending lane examines a WINDOW of ``PROBE_WIDTH`` consecutive chain
positions in one gather, and resolves at the first position that is
not an occupied mismatch:

- 4-word compare says "already present" → done, ``was_unknown=False``;
- first empty slot in the window → contend via a deterministic
  scatter-min election: contenders scatter their lane id into a claim
  scratch with ``.min`` (min is commutative — duplicate indices are
  safe and order-independent) and read it back; the surviving lane
  wins and writes key+meta (winners hold unique slots, so those
  scatters never see duplicate indices — XLA's duplicate-index
  scatter is specified per element, not per row, so a whole-row CAS
  could tear). Losers resolve IN the same round by comparing their key
  against the winner's — ``keys[claim[slot]]``, a batch-sized gather,
  never a second table-sized read: a match means a within-batch
  duplicate (done, ``was_unknown=False`` — first-in-lane-order wins,
  exactly Redis SADD semantics when the reference stores the same
  serial twice); a different key means the chain moved — probe on past
  the slot;
- all window positions occupied by other keys → ``r`` advances past
  the window.

Random-access ops (gather/scatter on the HBM-resident table) carry a
large fixed per-op cost on TPU, so the structure minimizes OP COUNT
per round (4 table-touching ops — the fused row commits key+meta in
one scatter — and no claim reset: a slot is contended
at most once per call) and ROUND COUNT (losers resolve in-round;
windows cover W chain positions per gather).

A key always lands at the FIRST empty slot of its probe chain (losers
never skip the contested slot), so ``contains``' probe-until-empty
lookup invariant holds.

Within-batch dedup therefore falls out of the probe loop itself — no
pre-pass needed (the previous design ran 33 full-batch lexsorts per
insert; this one runs zero sorts).

Lanes that exhaust ``max_probes`` (or the round budget) are reported
in ``overflowed``; the aggregator sends them down the exact host lane
(the same reject-to-host contract the reference uses for unparseable
entries, /root/reference/cmd/ct-fetch/ct-fetch.go:206-225).

Alongside each key a ``meta`` word (packed issuer index + expiry hour
offset, :mod:`ct_mapreduce_tpu.core.packing`) is stored so a drain can
reconstruct exact per-(issuer, expDate) serial counts without a second
device pass.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


# Chain positions examined per probe round (one gather). Wider windows
# resolve more lanes in round 1 (P(all W occupied) = load^W) at the
# price of a W-times-larger gather; env-tunable for hardware sweeps.
def _probe_width_from_env() -> int:
    raw = os.environ.get("CTMR_PROBE_WIDTH", "4")
    try:
        width = int(raw)
        if width < 1:
            raise ValueError
    except ValueError:
        # A malformed env var must not break `import ct_mapreduce_tpu`
        # for CLI paths that never probe; degrade to the default loudly.
        import warnings

        warnings.warn(
            f"ignoring CTMR_PROBE_WIDTH={raw!r} (want an int >= 1); "
            "using 4", stacklevel=2)
        return 4
    return width


PROBE_WIDTH = _probe_width_from_env()


class TableState(NamedTuple):
    """Dedup-set state living in HBM (donated through insert steps).

    One FUSED row per slot — 4 fingerprint words + the meta word —
    so a winning lane commits key AND meta in a single scatter
    (random-access table ops carry a large fixed cost on TPU; fusing
    the two writes cuts insert from 5 table-touching ops per probe
    round to 4). The all-zero KEY words mark an empty slot; meta of 0
    is legal data.
    """

    rows: jax.Array  # uint32[capacity, 5]: fp words 0..3, meta word 4
    count: jax.Array  # int32[]; occupied slots

    @property
    def keys(self) -> jax.Array:  # uint32[capacity, 4] view
        return self.rows[:, :4]

    @property
    def meta(self) -> jax.Array:  # uint32[capacity] view
        return self.rows[:, 4]


def make_table(capacity: int) -> TableState:
    if capacity & (capacity - 1):
        raise ValueError(f"capacity must be a power of two, got {capacity}")
    return TableState(
        rows=jnp.zeros((capacity, 5), dtype=jnp.uint32),
        count=jnp.zeros((), dtype=jnp.int32),
    )


def fuse_rows(keys, meta):
    """uint32[N, 4] + uint32[N] → fused uint32[N, 5] rows (works on
    NumPy and jax arrays alike)."""
    xp = jnp if isinstance(keys, jax.Array) else np
    return xp.concatenate(
        [keys.astype(xp.uint32), meta.astype(xp.uint32)[:, None]], axis=1
    )


def _home_slot(keys: jax.Array, capacity: int) -> jax.Array:
    """Initial probe slot from the fingerprint's first two words."""
    h = keys[:, 0] ^ (keys[:, 1] * np.uint32(0x9E3779B9))
    return (h & np.uint32(capacity - 1)).astype(jnp.int32)


def _probe_window(
    table_rows: jax.Array,
    keys: jax.Array,
    home: jax.Array,
    r: jax.Array,
    W: int,
    max_probes: int,
    chain_capacity: int,
):
    """One W-wide window of triangular-chain probes: the shared access
    pattern of ``insert`` and ``contains``.

    ``table_rows`` is the fused uint32[capacity, 5] table (or any
    row array whose first 4 words are the key); matching and the
    empty-slot test look only at the key words.

    Returns ``(slots [B, W], match_j [B, W], empty_j [B, W])`` with
    positions past ``max_probes`` masked out of both match and empty.
    """
    rj = r[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]  # [B, W]
    slots = (home[:, None] + (rj * (rj + 1)) // 2) & (chain_capacity - 1)
    in_budget = rj < max_probes
    cur = table_rows[slots][..., :4]  # [B, W, 4] key words of each row
    match_j = jnp.all(cur == keys[:, None, :], axis=-1) & in_budget
    empty_j = jnp.all(cur == 0, axis=-1) & in_budget
    return slots, match_j, empty_j


def _desentinel(keys: jax.Array) -> jax.Array:
    """Remap the (astronomically unlikely) all-zero fingerprint."""
    is_zero = jnp.all(keys == 0, axis=-1, keepdims=True)
    bump = jnp.concatenate(
        [jnp.zeros(keys.shape[:-1] + (3,), jnp.uint32),
         jnp.ones(keys.shape[:-1] + (1,), jnp.uint32)], axis=-1)
    return jnp.where(is_zero, bump, keys)


@functools.partial(jax.jit, static_argnames=("max_probes",), donate_argnums=(0,))
def insert(
    state: TableState,
    keys: jax.Array,
    meta: jax.Array,
    valid: jax.Array,
    max_probes: int = 32,
):
    """Batch insert-if-absent.

    Args:
      state: the table (donated; updated in place in HBM).
      keys: uint32[B, 4] fingerprints.
      meta: uint32[B] per-lane metadata scattered on successful insert.
      valid: bool[B]; padding lanes are ignored entirely.
      max_probes: probe rounds before declaring overflow.

    Returns:
      (new_state, was_unknown bool[B], overflowed bool[B]).
    """
    capacity = state.rows.shape[0]
    b = keys.shape[0]
    keys = _desentinel(keys.astype(jnp.uint32))
    qrows = fuse_rows(keys, meta)  # [B, 5]: what a winner commits
    home = _home_slot(keys, capacity)

    lane = jnp.arange(b, dtype=jnp.int32)
    no_lane = jnp.int32(2**31 - 1)
    W = min(PROBE_WIDTH, max_probes)
    # Every pending lane advances its probe index by ≥1 per round
    # (losers resolve in-round and skip past the contested slot), so
    # max_probes + 1 rounds bound the loop; lanes that leave the loop
    # still pending are overflow → exact host lane.
    max_rounds = max_probes + 1

    def cond(carry):
        rounds, _r, _rows, _claim, pending, _found, _inserted, _ovf = carry
        return (rounds < max_rounds) & jnp.any(pending)

    def round_body(carry):
        (rounds, r, table_rows, claim,
         pending, found, inserted, ovf) = carry
        # Probe window: W consecutive triangular-chain positions
        # starting at each lane's r, fetched in ONE gather.
        slots, match_j, empty_j = _probe_window(
            table_rows, keys, home, r, W, max_probes, capacity
        )
        stop_j = match_j | empty_j
        any_stop = jnp.any(stop_j, axis=-1)
        jstar = jnp.argmax(stop_j, axis=-1).astype(jnp.int32)  # first stop
        sel = jnp.take_along_axis  # alias
        match = pending & any_stop & sel(match_j, jstar[:, None], 1)[:, 0]
        empty = pending & any_stop & ~match
        slot = sel(slots, jstar[:, None], 1)[:, 0]
        # Deterministic election at each lane's first-empty slot:
        # scatter-min lane ids (min commutes ⇒ duplicate indices are
        # safe), read back; the surviving lane id is the winner. No
        # reset pass is needed: a slot is contended at most once per
        # insert call — its election always produces a winner, who
        # occupies it, so no later round can see it empty again.
        cslot = jnp.where(empty, slot, capacity)  # OOB rows are dropped
        claim = claim.at[cslot].min(lane, mode="drop")
        wlane = claim[slot]  # winning lane id at each contested slot
        winner = empty & (wlane == lane)
        # Winners hold unique slots, so this scatter sees no duplicate
        # indices; the FUSED row commits key and meta in ONE op (the
        # whole point of the fused layout — one fewer table-sized
        # random-access op per round).
        wslot = jnp.where(winner, slot, capacity)
        table_rows = table_rows.at[wslot].set(qrows, mode="drop")
        # Resolve election losers IN-ROUND (random-access ops have a
        # large fixed cost on TPU, so resolving here is far cheaper
        # than an extra round): the winner's key is keys[wlane] — a
        # BATCH-sized gather, never a second table-sized one. Losers
        # whose key equals the winner's are within-batch duplicates
        # (done, known); distinct-key losers probe on past the slot.
        wkeys = jnp.take(keys, jnp.clip(wlane, 0, b - 1), axis=0)  # [B, 4]
        loser = empty & ~winner
        loser_match = loser & jnp.all(wkeys == keys, axis=-1)
        found = found | match | loser_match
        inserted = inserted | winner
        pending = pending & ~match & ~winner & ~loser_match
        # Remaining pending lanes continue past what they examined:
        # distinct-key losers past the contested position, miss-through
        # lanes past the whole window.
        r = jnp.where(pending, jnp.where(any_stop, r + jstar + 1, r + W), r)
        # A lane that exhausts its probe chain is overflow — record it
        # and drop it from pending so the loop can terminate early.
        exhausted = pending & (r >= max_probes)
        ovf = ovf | exhausted
        pending = pending & ~exhausted
        return (rounds + 1, r, table_rows, claim,
                pending, found, inserted, ovf)

    pending0 = valid
    zeros = jnp.zeros((b,), bool)
    r0 = jnp.zeros((b,), jnp.int32)
    # Fresh capacity-sized claim scratch per call: a single ~4B/slot
    # broadcast fill (≈0.3 ms at 2^26 on v5e HBM, against a multi-ms
    # step) buys an election that needs no persistent state — the
    # persistent TableState stays just (rows, count), which the
    # checkpoint codec splits back into keys/meta for format
    # stability. Revisit only if profiles show the fill on the flame
    # graph.
    claim0 = jnp.full((capacity,), no_lane, dtype=jnp.int32)
    (_, _, table_rows, _, pending, found,
     inserted, ovf) = jax.lax.while_loop(
        cond, round_body,
        (jnp.int32(0), r0, state.rows, claim0,
         pending0, zeros, zeros, zeros),
    )

    was_unknown = inserted  # lanes that claimed a slot
    # Never found a home: probe chain exhausted, or still pending when
    # the round budget ran out (pathological contention) — either way
    # the exact host lane takes over.
    overflowed = ovf | pending
    new_count = state.count + jnp.sum(inserted, dtype=jnp.int32)
    return TableState(table_rows, new_count), was_unknown, overflowed


@functools.partial(jax.jit, static_argnames=("max_probes",))
def contains(state: TableState, keys: jax.Array, max_probes: int = 32) -> jax.Array:
    """Batch membership query (no mutation): bool[B].

    Same access structure as :func:`insert`: a W-wide window of chain
    positions per gather, with a ``while_loop`` that exits as soon as
    every lane has hit a match or an empty slot — the common case is
    ONE table gather, not ``max_probes`` of them (random-access table
    ops are latency-priced per lane on TPU: ~13-15 ns/lane measured on
    the July installation, git history)."""
    capacity = state.rows.shape[0]
    keys = _desentinel(keys.astype(jnp.uint32))
    home = _home_slot(keys, capacity)
    b = keys.shape[0]
    W = min(PROBE_WIDTH, max_probes)

    def cond(carry):
        r, found, open_ = carry
        return jnp.any(open_)

    def round_body(carry):
        r, found, open_ = carry
        _slots, match_j, empty_j = _probe_window(
            state.rows, keys, home, r, W, max_probes, capacity
        )
        found = found | (open_ & jnp.any(
            match_j & (jnp.cumsum(empty_j, axis=-1) == 0), axis=-1
        ))
        # A lane stays open only if every in-budget window position was
        # an occupied mismatch (chain continues past the window).
        still = open_ & ~jnp.any(match_j | empty_j, axis=-1)
        r = jnp.where(still, r + W, r)
        open_ = still & (r < max_probes)
        return r, found, open_

    _, found, _ = jax.lax.while_loop(
        cond, round_body,
        (jnp.zeros((b,), jnp.int32), jnp.zeros((b,), bool),
         jnp.ones((b,), bool)),
    )
    return found


def contains_np(table_rows: np.ndarray, keys: np.ndarray,
                max_probes: int = 32) -> np.ndarray:
    """NumPy mirror of :func:`contains` — same home slot, triangular
    chain, and match-before-first-empty invariant — for host-only
    snapshot reads (storage-statistics is pure host work and must not
    allocate device buffers or wait on TPU acquisition).

    ``table_rows`` may be the fused [capacity, 5] rows or a bare
    [capacity, 4] key array; only the key words are examined.

    Vectorized (drain probes every host-lane serial in one call), with
    the batch chunked to bound the [chunk, max_probes, 4] gather."""
    capacity = table_rows.shape[0]
    if capacity & (capacity - 1):
        raise ValueError(f"capacity must be a power of two, got {capacity}")
    keys = keys.astype(np.uint32, copy=True).reshape(-1, 4)
    zero = ~keys.any(axis=-1)
    keys[zero, :] = 0
    keys[zero, 3] = 1  # _desentinel
    mask = capacity - 1
    home = (keys[:, 0] ^ (keys[:, 1] * np.uint32(0x9E3779B9))).astype(np.int64)
    r = np.arange(max_probes, dtype=np.int64)
    tri = (r * (r + 1)) // 2
    out = np.zeros((keys.shape[0],), bool)
    table_keys = table_rows[:, :4]  # zero-copy view; gather keys only
    for start in range(0, keys.shape[0], 65536):
        sl = slice(start, start + 65536)
        slots = (home[sl, None] + tri[None, :]) & mask  # [b, P]
        rows = table_keys[slots]  # [b, P, 4] key words
        match = (rows == keys[sl, None, :]).all(axis=-1)
        empty = ~rows.any(axis=-1)
        out[sl] = (match & (np.cumsum(empty, axis=1) == 0)).any(axis=1)
    return out


def occupied(state: TableState) -> jax.Array:
    """bool[capacity] occupancy mask."""
    return jnp.any(state.keys != 0, axis=-1)


def drain_np(state: TableState) -> tuple[np.ndarray, np.ndarray]:
    """Pull (keys, meta) of occupied slots to host as NumPy arrays."""
    keys = np.asarray(state.keys)
    meta = np.asarray(state.meta)
    occ = keys.any(axis=-1)
    return keys[occ], meta[occ]
