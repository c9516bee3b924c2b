"""Bucketized device-resident dedup set: sort-based insert, tile-aligned rows.

Drop-in alternative to :mod:`ct_mapreduce_tpu.ops.hashtable` (same
Redis-SADD semantics as the reference's per-certificate ``WasUnknown``
round trip, /root/reference/storage/knowncertificates.go:38-55), built
from the primitives the hardware actually favors. Measured on one
v5e chip at 2^20 lanes (July installation; docs/randacc_r04_run.log):

  gather/scatter of 5-word rows:   13.6 / 86.5 ns per lane
  gather/scatter of 128-word rows: 12.0 / 11.8 ns per lane
  full 128-bit lexsort + payload:   4.0 ns per lane

i.e. random access costs per-LANE latency, not bandwidth — a 512-byte
tile-aligned block moves for the price of one word, while a 5-word
row scatter pays a ~7x tile-misalignment penalty — and sorts are
nearly free. So:

- The table is an array of BUCKETS: ``rows: uint32[n_buckets, 128]``,
  each row holding 24 slots x 5 words (4 fingerprint words + meta;
  word 120 caches the fill count, 121..127 spare) — one gather
  fetches a whole bucket, one scatter commits it, both tile-aligned.
- Slots fill contiguously (0..fill-1); the count ALSO rides in the
  row's spare word 120 for `contains` and host-side restores. (The
  insert still recomputes it by scanning — reading the cached word
  instead measured 2x SLOWER; see the _FILL_MODE note below.)
- Within-batch coordination is a SORT, not a scatter election: lanes
  sort by (bucket, key words, lane). Same-bucket lanes become
  adjacent, same-key lanes become adjacent-with-deterministic-first
  (lane order = batch order, matching the reference's sequential
  first-writer-wins), and every per-bucket quantity (fill, rank,
  merge window) is a dense segmented scan.
- Each round, every bucket's first pending lane (the bucket head)
  composes the merged row — old slots plus up to ``WINDOW`` new keys
  from its adjacent lanes — and commits it in ONE 128-word scatter.
- A bucket that is full (all 24 slots occupied, no key match) spills
  at BUCKET granularity: the lane hops to the next bucket (linear
  probing over buckets), up to ``max_probes`` hops, then overflows to
  the exact host lane — the reference's tolerate-and-redirect
  contract (/root/reference/cmd/ct-fetch/ct-fetch.go:206-225).

The lookup invariant mirrors slot-level open addressing one level up:
a key lives in the first non-full bucket of its hop chain, so
``contains`` probes until it hits a key match or a bucket with an
empty slot. Inserts only hop past a bucket when the round leaves it
with all 24 slots occupied, which preserves that invariant.

Load behavior (measured, docs/load_sweep_r04_bucket.log): 3.58M
entries/s at 25% load, 2.20M at 50%, 0.63M at 75%, 0.28M at 85% (131K
lanes, cap 2^24, one v5e). Below ~55% load inserts stay one
gather/sort/scatter round; past it the Poisson tail of full 24-slot
buckets (at 75% load a bucket is full ~10% of the time) forces hop
rounds at full batch width. The aggregator's growth policy therefore
grows at 55% fill by default, keeping steady state in the flat part
of the curve; versus the slot-granular table the bucket layout is
~3x faster at every load point measured (open table: 1.21M at 25%,
0.77M at 50%, 0.51M at 75%).
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

SLOTS = 24  # slots per bucket (24 * 5 = 120 of 128 row words)
ROW_WORDS = 128
#: Spare row word caching the bucket's occupied-slot count. Slots fill
#: contiguously, so the count used to be recomputed as a 24-iteration
#: occupancy scan over the gathered row every insert round (~5 ns/entry
#: of pure formulation cost, docs/profile_r04_step_ops.txt); caching it
#: here makes `fill` a single column read. Every code path that builds
#: rows outside `insert` (bulk_insert_np, checkpoint restore) must keep
#: this word consistent — `fill_counts_np` recomputes it from occupancy.
FILL_WORD = 120


def _window_from_env() -> int:
    # Default 6: measured best on v5e at 2^20 lanes (191.0/191.4
    # ns/entry full step on two runs, vs 196.5-196.9 at 8, 250 at 4,
    # 229 at 16 — 4 loses to extra rounds, 16 to compose width).
    raw = os.environ.get("CTMR_BUCKET_WINDOW", "6")
    try:
        w = int(raw)
        if not 1 <= w <= 32:
            raise ValueError
    except ValueError:
        import warnings

        warnings.warn(
            f"ignoring CTMR_BUCKET_WINDOW={raw!r} (want 1..32); using 6",
            stacklevel=2)
        return 6
    return w


#: New keys merged per bucket per round (adjacent-lane look-ahead).
WINDOW = _window_from_env()

#: Fill-count sourcing inside the insert round (perf bisect knob):
#:   scan      — recompute via the 24-slot occupancy scan (default;
#:               the fill word is still written, so the cache stays
#:               valid for `contains` and host-side restores)
#:   cache     — read row word FILL_WORD instead of scanning
#:   scan-only — occupancy scan AND skip the fill-word write (the
#:               exact round-4 program, for A/B timing)
#:
#: MEASURED (round 5, July installation, at 2^20 lanes / cap 2^26 on
#: one v5e; the probe is in git history): scan-only 65.8, scan 66.5, cache 133 ns/entry. Writing
#: the cached count is free; READING it in place of the occupancy
#: scan — the "obvious" win — DOUBLES insert cost (the single-column
#: read replaces a reduce that XLA fused into the gather, and the
#: resulting schedule materializes extra [B, 128] traffic). The scan
#: stays the shipping formulation; the cache word exists for
#: `contains`' emptiness test and topology-mismatched restores.
def _fill_mode_from_env() -> str:
    raw = os.environ.get("CTMR_FILL_MODE", "scan").strip().lower()
    if raw not in ("scan", "cache", "scan-only"):
        import warnings

        warnings.warn(
            f"ignoring CTMR_FILL_MODE={raw!r} "
            "(want scan | cache | scan-only); using scan", stacklevel=2)
        return "scan"
    return raw


_FILL_MODE = _fill_mode_from_env()


class BucketTable(NamedTuple):
    """Dedup-set state in HBM (donated through insert steps).

    ``rows[b]`` is bucket ``b``: 24 slots x (4 fingerprint words +
    meta word), filled contiguously; all-zero KEY words mark an empty
    slot (meta 0 is legal data, exactly as in hashtable.TableState).
    Row word ``FILL_WORD`` caches the bucket's occupied-slot count.
    """

    rows: jax.Array  # uint32[n_buckets, 128]
    count: jax.Array  # int32[]; occupied slots

    @property
    def n_buckets(self) -> int:
        return self.rows.shape[0]

    @property
    def capacity(self) -> int:
        return self.rows.shape[0] * SLOTS

    # Positional slot views matching hashtable.TableState's properties,
    # so the checkpoint codec writes the same (keys, meta) format for
    # both layouts (slot i = bucket i // SLOTS, position i % SLOTS).
    # Computed on HOST: a device-side [N, 5] reshape would pad its
    # minor dim to 128 lanes (25.6x the table's HBM footprint).
    @property
    def keys(self):  # uint32[n_buckets * SLOTS, 4]
        rows = np.asarray(self.rows)
        return rows[:, : SLOTS * 5].reshape(-1, 5)[:, :4]

    @property
    def meta(self):  # uint32[n_buckets * SLOTS]
        rows = np.asarray(self.rows)
        return rows[:, : SLOTS * 5].reshape(-1, 5)[:, 4]


def bucket_count(capacity: int, max_capacity: int | None = None) -> int:
    """Power-of-two bucket count for ≥ ``capacity`` slots. When the
    rounded-up slot count would exceed ``max_capacity`` (rows are 512 B
    each, so a silent 2x round-up can double HBM use past the
    configured bound), rounds DOWN instead."""
    if capacity < 1:
        raise ValueError(f"capacity must be positive, got {capacity}")
    nb = 1 << max(0, (capacity + SLOTS - 1) // SLOTS - 1).bit_length()
    if max_capacity is not None and nb * SLOTS > max_capacity:
        while nb > 1 and nb * SLOTS > max_capacity:
            nb >>= 1
    return nb


def make_table(capacity: int, max_capacity: int | None = None) -> BucketTable:
    """Table with at least ``capacity`` slots (n_buckets rounds up to
    a power of two; real capacity is ``state.capacity``). Pass
    ``max_capacity`` to round down instead when the power-of-two
    round-up would overshoot a configured ceiling."""
    n_buckets = bucket_count(capacity, max_capacity)
    return BucketTable(
        rows=jnp.zeros((n_buckets, ROW_WORDS), dtype=jnp.uint32),
        count=jnp.zeros((), dtype=jnp.int32),
    )


def fill_counts_np(rows_np: np.ndarray) -> np.ndarray:
    """Recompute each bucket's occupied-slot count from key-word
    occupancy and write it into ``FILL_WORD`` in place. Call after any
    host-side row construction (checkpoint restore, bulk insert) so
    the device insert's cached-fill invariant holds."""
    slots = rows_np[:, : SLOTS * 5].reshape(rows_np.shape[0], SLOTS, 5)
    fills = slots[:, :, :4].any(axis=-1).sum(axis=-1).astype(np.uint32)
    rows_np[:, FILL_WORD] = fills
    return fills


def _desentinel(keys: jax.Array) -> jax.Array:
    """Remap the (2^-128-unlikely) all-zero fingerprint, mirroring
    hashtable._desentinel so both tables share key semantics."""
    is_zero = jnp.all(keys == 0, axis=-1, keepdims=True)
    bump = jnp.concatenate(
        [jnp.zeros(keys.shape[:-1] + (3,), jnp.uint32),
         jnp.ones(keys.shape[:-1] + (1,), jnp.uint32)], axis=-1)
    return jnp.where(is_zero, bump, keys)


def _home_bucket(keys: jax.Array, n_buckets: int) -> jax.Array:
    h = keys[:, 0] ^ (keys[:, 1] * np.uint32(0x9E3779B9))
    # Independent of the in-bucket layout; distinct from hashtable's
    # slot hash only through the modulus.
    return (h & np.uint32(n_buckets - 1)).astype(jnp.int32)


def _shift_up(a: jax.Array, j: int, fill) -> jax.Array:
    """a[i + j] with ``fill`` past the end (j >= 0 static)."""
    n = a.shape[0]
    if j == 0:
        return a
    if j >= n:
        return jnp.full_like(a, fill)
    pad = jnp.full((j,) + a.shape[1:], fill, dtype=a.dtype)
    return jnp.concatenate([a[j:], pad], axis=0)


@functools.partial(jax.jit, static_argnames=("max_probes",), donate_argnums=(0,))
def insert(
    state: BucketTable,
    keys: jax.Array,
    meta: jax.Array,
    valid: jax.Array,
    max_probes: int = 32,
):
    """Batch insert-if-absent. Same contract as ``hashtable.insert``:

    Returns ``(new_state, was_unknown bool[B], overflowed bool[B])``
    with ``was_unknown`` true for the first lane (in batch order) of
    each genuinely-new key, false for re-inserts and within-batch
    duplicates; ``overflowed`` lanes must take the exact host lane.
    ``max_probes`` bounds bucket HOPS (each hop skips a full bucket =
    24 slots, so chains are far shorter than slot-granular probing).
    """
    rows = state.rows
    nb = rows.shape[0]
    b = keys.shape[0]
    if b > 1 << 25:
        # The segment broadcast packs (sorted position, window count)
        # as idx * 64 + w into one int32 cummax; position 2^25 is where
        # that encoding would overflow and silently corrupt merges.
        raise ValueError(
            f"insert batch width {b} exceeds 2^25 lanes (the int32 "
            "segment-broadcast encoding); split the batch")
    keys = _desentinel(keys.astype(jnp.uint32))
    h0 = _home_bucket(keys, nb)
    lane = jnp.arange(b, dtype=jnp.int32)
    sentinel = jnp.int32(nb)  # resolved lanes sort past every bucket
    idx = lane  # alias: position index in sorted order

    # Per-lane flags packed into one sort payload word:
    # bit0 known (seen before), bit1 inserted, bits 8.. hop count.
    K_KNOWN = jnp.uint32(1)
    K_INS = jnp.uint32(2)
    HOP_1 = jnp.uint32(256)

    # Round budget: each round commits >= 1 new key per active bucket
    # (the bucket head is always in its own window), so only window
    # retries and hops consume rounds. Hops are bounded by max_probes;
    # a few extra rounds absorb window-limited retries on skewed
    # batches before the overflow contract hands lanes to the host.
    max_rounds = max_probes + 16

    def cond(carry):
        rounds = carry[0]
        h = carry[2]
        return (rounds < max_rounds) & jnp.any(h < sentinel)

    def round_body(carry):
        rounds, rows, h, k0, k1, k2, k3, mt, ln, flags = carry

        # Sort pending lanes by (bucket, key, lane): same-bucket lanes
        # adjacent, same-key lanes adjacent with batch-order-first;
        # resolved lanes (h == sentinel) sink to the end.
        h, k0, k1, k2, k3, ln, mt, flags = jax.lax.sort(
            (h, k0, k1, k2, k3, ln, mt, flags), num_keys=6)
        pend = h < sentinel
        kw = (k0, k1, k2, k3)

        # One tile-aligned gather per lane: the whole bucket.
        #
        # LAYOUT RULE for everything below: intermediates stay either
        # 1-D [B] or full-width [B, 128]. Any [B, small] array (a
        # stack/concat of columns, a [B, SLOTS, 5] reshape) pads its
        # minor dim to 128 lanes on TPU — measured 62 GB of padding at
        # 2^20 lanes for the stacked formulation of this very loop.
        row = rows[jnp.minimum(h, nb - 1)]  # [B, 128]

        # Occupancy: the cached fill word, or the 24-slot scan
        # (CTMR_FILL_MODE bisect knob). The match scan walks all 24
        # slots either way: empty slots are all-zero and keys are
        # desentineled nonzero, so matching against them is harmless.
        scan_fill = jnp.zeros((b,), jnp.int32)
        in_row = jnp.zeros((b,), bool)
        for s in range(SLOTS):
            w = [row[:, s * 5 + i] for i in range(4)]
            if _FILL_MODE != "cache":
                occ_s = (w[0] | w[1] | w[2] | w[3]) != 0
                scan_fill = scan_fill + occ_s.astype(jnp.int32)
            in_row = in_row | (
                (w[0] == k0) & (w[1] == k1) & (w[2] == k2) & (w[3] == k3))
        if _FILL_MODE == "cache":
            fill = row[:, FILL_WORD].astype(jnp.int32)
        else:
            fill = scan_fill
        in_row = pend & in_row

        # Segment structure over the sorted order (dense scans only).
        def prev(a, fillv):
            return jnp.concatenate(
                [jnp.full((1,), fillv, a.dtype), a[:-1]])

        bucket_head = pend & (h != prev(h, -1))
        key_diff = (
            (k0 != prev(k0, 0)) | (k1 != prev(k1, 0))
            | (k2 != prev(k2, 0)) | (k3 != prev(k3, 0)))
        key_head = pend & (bucket_head | key_diff)
        dup_lane = pend & ~key_head  # same key as an earlier lane
        new_head = key_head & ~in_row

        # Rank among new heads within my bucket segment (cumsum with a
        # cummax-propagated segment base — c is nondecreasing, so the
        # latest bucket head always wins the max).
        x = new_head.astype(jnp.int32)
        c = jnp.cumsum(x)
        base = jax.lax.cummax(jnp.where(bucket_head, c - x, -1))
        r = c - x - base  # 0-based new-key rank in segment

        # Head computes how many new keys land in its WINDOW-lane
        # look-ahead, then broadcasts (start index, count) down the
        # segment through one monotone cummax.
        same_seg_w = jnp.zeros((b,), jnp.int32)
        for j in range(WINDOW):
            nh_j = _shift_up(new_head, j, False)
            h_j = _shift_up(h, j, sentinel)
            same_seg_w = same_seg_w + (nh_j & (h_j == h)).astype(jnp.int32)
        enc = jnp.where(bucket_head, idx * 64 + jnp.minimum(same_seg_w, 63),
                        -1)
        cm = jax.lax.cummax(enc)
        bs = cm // 64  # my bucket head's sorted position
        w_seg = cm % 64  # new keys in the head's window
        pos = idx - bs  # my offset inside the segment

        # Merge decision, identical arithmetic for the head composing
        # the row and for each candidate judging itself: in-window new
        # heads hold consecutive ranks 0..w_seg-1, so `fill + r` is
        # exactly the slot a merged key occupies.
        space = SLOTS - fill
        merged = new_head & (pos < WINDOW) & (r < space)
        full_after = w_seg >= space  # bucket leaves this round full

        # Compose merged rows at bucket heads as ONE fused elementwise
        # expression over the [B, 128] row: candidate j of a head
        # writes its 5 words at columns tgt_j*5 .. tgt_j*5+4. Every
        # [B]-vector broadcasts along the lane axis inside the fusion
        # (no [B, 1] materialization — see the layout rule above), and
        # candidates hold distinct slots, so the wheres commute.
        #
        # NOTE (round-5 negative result, an insert-cost A/B on one v5e,
        # July installation, git history): a "cheaper" two-pass variant — build each
        # lane's own candidate block once, then OR the WINDOW-1
        # following lanes' blocks into the head via [B, 128] row shifts
        # — DOUBLED insert cost (130 vs 66 ns/entry at 2^20 lanes).
        # Sublane-axis shifts of [B, 128] arrays are not tile-aligned,
        # so each shifted copy materializes and the big loop fusion
        # breaks. The WINDOW-unrolled select chain below stays the
        # shipping formulation.
        col = jnp.arange(ROW_WORDS, dtype=jnp.int32)[None, :]  # [1, 128]
        outrow = row
        for j in range(WINDOW):
            m_j = _shift_up(merged, j, False)
            bs_j = _shift_up(bs, j, -1)
            ok_j = m_j & (bs_j == idx)  # candidate belongs to MY segment
            r_j = _shift_up(r, j, 0)
            tgt = fill + r_j
            off = col - (tgt * 5)[:, None]  # [B, 128]
            val = jnp.where(
                off == 0, _shift_up(k0, j, jnp.uint32(0))[:, None],
                jnp.where(
                    off == 1, _shift_up(k1, j, jnp.uint32(0))[:, None],
                    jnp.where(
                        off == 2, _shift_up(k2, j, jnp.uint32(0))[:, None],
                        jnp.where(
                            off == 3,
                            _shift_up(k3, j, jnp.uint32(0))[:, None],
                            _shift_up(mt, j, jnp.uint32(0))[:, None]))))
            sel = ok_j[:, None] & (off >= 0) & (off < 5)
            outrow = jnp.where(sel, val, outrow)
        # The committed row also carries the updated fill count in its
        # spare word (all w_seg in-window new keys hold consecutive
        # ranks, so exactly min(w_seg, space) of them merge per round).
        if _FILL_MODE != "scan-only":
            new_fill = (fill + jnp.minimum(w_seg, space)).astype(jnp.uint32)
            outrow = jnp.where(col == FILL_WORD, new_fill[:, None], outrow)

        # One tile-aligned scatter per active bucket (heads hold
        # unique, sorted bucket ids — no duplicate indices).
        write = bucket_head & (w_seg > 0) & (space > 0)
        wslot = jnp.where(write, h, sentinel)
        rows = rows.at[wslot].set(outrow, mode="drop")

        # Resolve lanes. Duplicate lanes resolve as known even when
        # their key head is still pending: the head (or, on overflow,
        # the exact host lane) accounts for the single fresh insert.
        flags = jnp.where(pend & (in_row | dup_lane), flags | K_KNOWN, flags)
        flags = jnp.where(merged, flags | K_INS, flags)
        resolved = in_row | dup_lane | merged
        still = pend & ~resolved
        hop = still & full_after
        flags = jnp.where(hop, flags + HOP_1, flags)
        hops = (flags >> 8).astype(jnp.int32)
        ovf_now = hop & (hops >= max_probes)
        # Overflowed lanes resolve (host lane takes them); hopping
        # lanes advance one bucket; window-limited lanes retry.
        h = jnp.where(still & ~ovf_now,
                      jnp.where(hop, (h + 1) & (nb - 1), h), sentinel)
        # Mark terminal overflow in a flag bit (bit2).
        flags = jnp.where(ovf_now, flags | jnp.uint32(4), flags)
        return (rounds + 1, rows, h, k0, k1, k2, k3, mt, ln, flags)

    h_init = jnp.where(valid, h0, sentinel)
    flags0 = jnp.zeros((b,), jnp.uint32)
    carry = (jnp.int32(0), rows, h_init,
             keys[:, 0], keys[:, 1], keys[:, 2], keys[:, 3],
             meta.astype(jnp.uint32), lane, flags0)
    (_, rows, h_fin, _, _, _, _, _, ln_fin, flags_fin) = jax.lax.while_loop(
        cond, round_body, carry)

    # Unsort the per-lane outcome by SORTING on the carried lane ids
    # (a permutation of 0..b-1, so the sort reproduces lane order
    # exactly). A sort is the cheap primitive on this hardware — 2.6
    # vs 13 ns/lane for the equivalent scatter (July installation).
    # Lanes that left the loop still pending (round budget) also
    # overflow.
    res_sorted = (
        flags_fin
        | jnp.where(h_fin < sentinel, jnp.uint32(4), jnp.uint32(0)))
    _, packed = jax.lax.sort((ln_fin, res_sorted), num_keys=1)
    was_unknown = (packed & 2) != 0
    overflowed = (packed & 4) != 0
    new_count = state.count + jnp.sum(was_unknown, dtype=jnp.int32)
    return BucketTable(rows, new_count), was_unknown, overflowed


@functools.partial(jax.jit, static_argnames=("max_probes",))
def contains(state: BucketTable, keys: jax.Array,
             max_probes: int = 32) -> jax.Array:
    """Batch membership query: bool[B]. One bucket gather resolves a
    lane unless the bucket is full-without-match (then it hops, like
    the insert's bucket-granular open addressing)."""
    rows = state.rows
    nb = rows.shape[0]
    b = keys.shape[0]
    keys = _desentinel(keys.astype(jnp.uint32))
    h0 = _home_bucket(keys, nb)

    def cond(carry):
        hops, _h, open_, _found = carry[0], carry[1], carry[2], carry[3]
        return (hops < max_probes) & jnp.any(open_)

    def round_body(carry):
        hops, h, open_, found = carry
        row = rows[h]  # [B, 128]
        # Per-column [B] slices, not a [B, SLOTS, 5] reshape — small
        # minor dims pad to 128 lanes on TPU (layout rule in insert).
        # Emptiness comes from the cached fill word, not a slot scan.
        match = jnp.zeros((b,), bool)
        has_empty = jnp.zeros((b,), bool)
        for s in range(SLOTS):
            w = [row[:, s * 5 + i] for i in range(4)]
            match = match | (
                (w[0] == keys[:, 0]) & (w[1] == keys[:, 1])
                & (w[2] == keys[:, 2]) & (w[3] == keys[:, 3]))
            if _FILL_MODE == "scan-only":
                has_empty = has_empty | ((w[0] | w[1] | w[2] | w[3]) == 0)
        if _FILL_MODE != "scan-only":
            has_empty = row[:, FILL_WORD].astype(jnp.int32) < SLOTS
        found = found | (open_ & match)
        open_ = open_ & ~match & ~has_empty
        h = jnp.where(open_, (h + 1) & (nb - 1), h)
        return hops + 1, h, open_, found

    _, _, _, found = jax.lax.while_loop(
        cond, round_body,
        (jnp.int32(0), h0, jnp.ones((b,), bool), jnp.zeros((b,), bool)))
    return found


def contains_np(rows_np: np.ndarray, keys: np.ndarray,
                max_probes: int = 32) -> np.ndarray:
    """NumPy mirror of :func:`contains` for host-only snapshot reads
    (storage-statistics must not touch the device)."""
    nb = rows_np.shape[0]
    keys = keys.astype(np.uint32, copy=True).reshape(-1, 4)
    zero = ~keys.any(axis=-1)
    keys[zero, 3] = 1  # _desentinel
    h = ((keys[:, 0] ^ (keys[:, 1] * np.uint32(0x9E3779B9)))
         & np.uint32(nb - 1)).astype(np.int64)
    out = np.zeros((keys.shape[0],), bool)
    open_ = np.ones((keys.shape[0],), bool)
    slots = rows_np[:, : SLOTS * 5].reshape(nb, SLOTS, 5)
    for _ in range(max_probes):
        if not open_.any():
            break
        rows = slots[h[open_]]  # [n, SLOTS, 5]
        match = (rows[:, :, :4] == keys[open_][:, None, :]).all(-1).any(-1)
        has_empty = (~rows[:, :, :4].any(-1)).any(-1)
        sub = np.where(open_)[0]
        out[sub[match]] = True
        still = ~match & ~has_empty
        open_[sub[~still]] = False
        h[sub[still]] = (h[sub[still]] + 1) & (nb - 1)
    return out


def drain_np(state: BucketTable) -> tuple[np.ndarray, np.ndarray]:
    """Pull (keys uint32[N, 4], meta uint32[N]) of occupied slots."""
    rows = np.asarray(state.rows)
    slots = rows[:, : SLOTS * 5].reshape(-1, 5)
    occ = slots[:, :4].any(axis=-1)
    return slots[occ, :4], slots[occ, 4]


def bulk_insert_np(rows_np: np.ndarray, keys: np.ndarray,
                   meta: np.ndarray, max_probes: int = 32) -> int:
    """Host-side rebuild: insert ``keys`` into ``rows_np`` in place
    (the topology-mismatched checkpoint-restore path). Returns the
    number of keys that could not be placed within ``max_probes``
    hops. Callers must pass DEDUPLICATED keys not already present in
    the table (drained dedup-set rows satisfy both by construction) —
    no membership check is performed.

    Vectorized by rounds: bucket fills via bincount, per-bucket ranks
    via argsort order, spillover hops to the next bucket. Maintains
    the ``FILL_WORD`` cache the device insert relies on.
    """
    nb = rows_np.shape[0]
    keys = keys.astype(np.uint32).reshape(-1, 4)
    meta = meta.astype(np.uint32).reshape(-1)
    zero = ~keys.any(axis=-1)
    if zero.any():
        keys = keys.copy()
        keys[zero, 3] = 1
    h = ((keys[:, 0] ^ (keys[:, 1] * np.uint32(0x9E3779B9)))
         & np.uint32(nb - 1)).astype(np.int64)
    slots = rows_np[:, : SLOTS * 5].reshape(nb, SLOTS, 5)
    fill = (slots[:, :, :4].any(axis=-1)).sum(axis=-1).astype(np.int64)
    alive = np.ones(keys.shape[0], bool)
    for _ in range(max_probes):
        if not alive.any():
            break
        sub = np.where(alive)[0]
        order = sub[np.argsort(h[sub], kind="stable")]
        hs = h[order]
        seg_start = np.r_[True, hs[1:] != hs[:-1]]
        seg_idx = np.cumsum(seg_start) - 1
        first = np.where(seg_start)[0]
        rank = np.arange(len(order)) - first[seg_idx]
        slot = fill[hs] + rank
        ok = slot < SLOTS
        tgt = order[ok]
        slots[hs[ok], slot[ok], :4] = keys[tgt]
        slots[hs[ok], slot[ok], 4] = meta[tgt]
        fill += np.bincount(hs[ok], minlength=nb)
        alive[tgt] = False
        h[order[~ok]] = (h[order[~ok]] + 1) & (nb - 1)
    rows_np[:, FILL_WORD] = fill.astype(np.uint32)
    return int(alive.sum())


# -- packing for a full checkpoint ------------------------------------------
#
# A full base holds the occupied slots, and only they leave the device.
# Slots fill contiguously, so a bucket's occupied slots are its first
# ``fill``; the packed form is every bucket's fill beside the occupied
# slots' five words in bucket order. The device produces it a CHUNK of
# output rows at a time (one program, the chunk's first row traced):
# the work and the bytes follow the occupied count, not the capacity.

#: Output rows of one packed chunk (20 B a row: 5.2 MB a chunk).
PACK_CHUNK = 1 << 18
_PACK_RADIX = 128  # one tile-aligned row of the search index
_PACK_TOP = 1024  # index entries compared densely, without a gather


def pack_chunk_rows(n_buckets: int) -> int:
    """Rows of a packed chunk for a table of ``n_buckets`` (a table
    smaller than ``PACK_CHUNK`` is one chunk)."""
    return min(PACK_CHUNK, n_buckets * SLOTS)


def _running_index(fill: jax.Array):
    """The search structure over a per-bucket count: the inclusive
    running sum of ``fill`` as rows of 128, then every row's last entry
    again as rows of 128, and so on down to at most 1,024 entries."""
    cur = jnp.cumsum(fill, dtype=jnp.int32)
    index = []
    while cur.shape[0] > _PACK_TOP:
        level = cur.reshape(-1, _PACK_RADIX)
        index.append(level)
        cur = level[:, -1]
    index.append(cur)
    return tuple(index)


def _locate(index, j: jax.Array):
    """``(g, base)`` for counted items ``j``: the bucket an item lives
    in = how many buckets' running sums are at most ``j``, found coarse
    to fine (a dense compare against the top of ``index``, then one
    128-entry row gather a level), and the largest running sum at most
    ``j``: the items before that bucket."""
    top = index[-1]
    le = top[None, :] <= j[:, None]
    g = jnp.sum(le, axis=1, dtype=jnp.int32)
    base = jnp.max(jnp.where(le, top[None, :], 0), axis=1)
    for level in reversed(index[:-1]):
        row = level[jnp.minimum(g, level.shape[0] - 1)]  # [chunk, 128]
        le = row <= j[:, None]
        g = g * _PACK_RADIX + jnp.sum(le, axis=1, dtype=jnp.int32)
        base = jnp.maximum(base, jnp.max(jnp.where(le, row, 0), axis=1))
    return g, base


def pack_index(rows: jax.Array):
    """``(fill uint8[nb], index)`` of a table's rows, traceable.

    ``fill`` is the cached ``FILL_WORD`` column: one strided read, no
    temporary of the table's size (recounting occupancy from the key
    words materialized 2 GB of lane-shifted flags under the v5e
    compiler). A caller that must not trust the cache compares
    ``index[-1][-1]``, the occupied count the fills add up to, with
    the table's own ``count``. ``index`` is the search structure
    :func:`pack_chunk` walks (:func:`_running_index`)."""
    fill = jnp.minimum(rows[:, FILL_WORD], SLOTS).astype(jnp.int32)
    return fill.astype(jnp.uint8), _running_index(fill)


def pack_chunk(rows: jax.Array, index, start: jax.Array, chunk: int):
    """Occupied slots ``start .. start+chunk`` (in bucket order) as
    ``uint32[5 * chunk]``: the chunk's first key words, then its
    second, third and fourth, then its meta words (word-major and flat:
    no small minor dimension, and no sublane padding of five rows to
    eight, so a chunk is 20 B a row in HBM too). Rows past the occupied
    count read 0.

    Output row ``j`` lives in the bucket :func:`_locate` finds; its slot
    is ``j`` less the largest running sum at most ``j``. One gather of
    the bucket's row and five masked lane sums pick the slot's words."""
    nb = rows.shape[0]
    j = start + jnp.arange(chunk, dtype=jnp.int32)
    g, base = _locate(index, j)
    live = j < index[-1][-1]
    bucket = rows[jnp.minimum(g, nb - 1)]  # [chunk, 128]
    off = (jnp.arange(ROW_WORDS, dtype=jnp.int32)[None, :]
           - ((j - base) * 5)[:, None])
    words = [
        jnp.where(live, jnp.sum(jnp.where(off == i, bucket, 0), axis=1,
                                dtype=jnp.uint32), 0)
        for i in range(5)]
    return jnp.concatenate(words)


pack_index_jit = jax.jit(pack_index)
pack_chunk_jit = jax.jit(pack_chunk, static_argnames=("chunk",))


def packed_ends(fill: np.ndarray, keys: np.ndarray,
                meta: np.ndarray) -> np.ndarray:
    """The inclusive running sum of a packed base's ``fill`` (int64), or
    the ``ValueError`` of a base whose fills do not add up to its rows:
    the check every reader of a packed base makes before it builds, or
    puts, anything."""
    ends = np.cumsum(fill, dtype=np.int64)
    total = int(ends[-1]) if ends.shape[0] else 0
    if total != keys.shape[0] or keys.shape[0] != meta.shape[0]:
        raise ValueError(
            f"packed base: {keys.shape[0]} keys and {meta.shape[0]} meta "
            f"words for fills that sum to {total}")
    if total and int(fill.max()) > SLOTS:
        raise ValueError(
            f"packed base: a fill of {int(fill.max())} where a bucket "
            f"has {SLOTS} slots")
    return ends


def unpack_np(fill: np.ndarray, keys: np.ndarray,
              meta: np.ndarray) -> np.ndarray:
    """The rows a packed base was packed from, fill word included:
    bucket ``b`` takes the next ``fill[b]`` of ``keys`` / ``meta`` into
    its first slots. The plain reference of :func:`unpack_rows`, and
    what a reader that may not touch a device builds its table with."""
    nb = fill.shape[0]
    ends = packed_ends(fill, keys, meta)
    fill = fill.astype(np.int64)
    rows = np.zeros((nb, ROW_WORDS), np.uint32)
    bucket = np.repeat(np.arange(nb), fill)
    slot = np.arange(keys.shape[0]) - np.repeat(ends - fill, fill)
    slots = rows[:, : SLOTS * 5].reshape(nb, SLOTS, 5)
    slots[bucket, slot, :4] = keys
    slots[bucket, slot, 4] = meta
    rows[:, FILL_WORD] = fill
    return rows


# -- a packed base, unpacked on the device ------------------------------------
#
# The save's mirror: the packed stream goes to the device as the file
# holds it (``keys`` flat as rows of 128 lanes, 32 packed rows each;
# ``meta`` flat, 128 a row; a byte a bucket of ``fill``) and the bucket
# rows are built there. Bucket ``b``'s row is a window of the stream:
# its key words are flat words ``4 * base[b] ..`` (96 at most, so they
# lie in two adjacent 128-lane rows), its meta words are
# ``base[b] ..`` (24 at most: two adjacent rows again), ``base`` the
# exclusive running sum of ``fill``. Two aligned row gathers, one
# select and one lane roll by the window's offset bring each window to
# lane 0; a fixed spread then moves key word ``4s + w`` to lane
# ``5s + w`` and meta word ``s`` to lane ``5s + 4``. Every intermediate
# is a full [B, 128] row or a [B] vector; no column is sliced out of a
# row (the layout rule of the growth, below).
#
# The stream goes over a PIECE at a time (a fixed count of packed rows
# and a halo, zero-padded at the stream's end), so that no compiled
# shape follows the row count and a piece's transfer rides beside the
# unpack of the one before. A piece builds the buckets whose first row
# lies in it, a block of buckets at a time, into the table it is given
# (donated): the table the constructor made is the one restored.

#: Packed rows of one piece (20 B a row: 84 MB a piece).
UNPACK_PIECE = 1 << 22
#: Packed rows a piece carries past its end: the bucket that starts on
#: a piece's last row ends 23 rows later (a whole 128-lane row of meta).
UNPACK_HALO = 128
#: Buckets one step of the piece's loop builds.
UNPACK_BLOCK = 8192


def unpack_piece_rows(n_buckets: int) -> int:
    """Packed rows of a piece for a table of ``n_buckets`` (a table
    smaller than ``UNPACK_PIECE`` is one piece), in whole 128-lane rows
    of meta words."""
    return min(UNPACK_PIECE, -(-n_buckets * SLOTS // ROW_WORDS) * ROW_WORDS)


def _spread_steps(src: np.ndarray, dst: np.ndarray):
    """The conditional rolls to the right, ``[(k, arrives bool[128])]``,
    that take lane ``src[i]`` of a row to lane ``dst[i]`` for every
    ``i`` (distances that never fall along the row, highest bit first:
    the word-parallel expand, :func:`_compact` backwards). Worked out
    here once, in NumPy: the pattern is every row's."""
    at = np.full(ROW_WORDS, -1)
    dist = np.zeros(ROW_WORDS, np.int64)
    at[src], dist[src] = np.arange(src.shape[0]), dst - src
    steps = []
    for bit in reversed(range(7)):
        k = 1 << bit
        came_at, came_dist = np.roll(at, k), np.roll(dist, k)
        arrives = (came_at >= 0) & ((came_dist & k) != 0)
        leaves = (at >= 0) & ((dist & k) != 0)
        if not arrives.any():
            continue
        if (arrives & (at >= 0) & ~leaves).any():
            raise AssertionError("two lanes of a spread meet")
        at = np.where(arrives, came_at, np.where(leaves, -1, at))
        dist = np.where(arrives, came_dist - k, np.where(leaves, 0, dist))
        steps.append((k, arrives))
    if not np.array_equal(at[dst], np.arange(src.shape[0])):
        raise AssertionError("a spread that does not arrive")
    return steps


_KEY_LANES = np.arange(SLOTS * 4)
#: Key word ``4s + w`` of a window at lane 0 to lane ``5s + w``.
_KEY_SPREAD = _spread_steps(_KEY_LANES, _KEY_LANES + _KEY_LANES // 4)
#: Meta word ``s`` of a window at lane 4 to lane ``5s + 4``: the
#: distance ``4s`` has the key spread's five bits, two places up.
_META_AT = 4
_META_SPREAD = _spread_steps(np.arange(SLOTS) + _META_AT,
                             np.arange(SLOTS) * 5 + 4)
_META_LANES = np.isin(np.arange(ROW_WORDS), np.arange(SLOTS) * 5 + 4)


def _window_to_lane0(lane, first, second, offset, bits, shift=0):
    """Rows whose lane ``i`` is word ``offset + i - shift`` of
    ``first`` and ``second`` laid end to end (``offset`` int32[B, 1],
    under 128 and with only ``bits`` set): one select between the two,
    then a roll to the left by ``offset - shift``, a conditional roll a
    bit."""
    x = jnp.where(lane < offset, second, first)
    amount = (offset - shift) & (ROW_WORDS - 1)
    for bit in bits:
        x = jnp.where((amount & (1 << bit)) != 0,
                      jnp.roll(x, -(1 << bit), axis=1), x)
    return x


def _spread(x, steps):
    for k, arrives in steps:
        x = jnp.where(arrives[None, :], jnp.roll(x, k, axis=1), x)
    return x


@functools.partial(jax.jit, static_argnames=("block",), donate_argnums=(0,))
def unpack_rows(rows: jax.Array, fill: jax.Array, base: jax.Array,
                keys_piece: jax.Array, meta_piece: jax.Array,
                start: jax.Array, lo: jax.Array, hi: jax.Array, *,
                block: int):
    """``rows`` (donated) with buckets ``lo .. hi`` built from one piece
    of a packed base; XLA module ``jit_unpack_rows``.

    ``fill`` uint8[nb] and ``base`` int32[nb] (its exclusive running
    sum) are the whole table's; ``keys_piece`` uint32[(P + halo) / 32,
    128] and ``meta_piece`` uint32[(P + halo) / 128, 128] are packed
    rows ``start .. start + P + halo`` as the file holds them;
    ``lo .. hi`` are the buckets whose first packed row lies in
    ``start .. start + P`` (every other bucket of a block keeps the row
    it has). Shapes follow the bucket count and the piece: none follows
    the row count."""
    piece = meta_piece.shape[0] * ROW_WORDS - UNPACK_HALO
    lane = jax.lax.broadcasted_iota(jnp.int32, (block, ROW_WORDS), 1)
    meta_lane = jnp.asarray(_META_LANES)[None, :]

    def build(blk, rows):
        b0 = blk * block
        f = jax.lax.dynamic_slice(fill, (b0,), (block,)).astype(jnp.int32)
        at = jax.lax.dynamic_slice(base, (b0,), (block,)) - start
        mine = (at >= 0) & (at < piece)
        at = jnp.clip(at, 0, piece - 1)
        word = at * 4
        kq, mq = word >> 7, at >> 7
        keys = _window_to_lane0(
            lane, keys_piece[kq], keys_piece[kq + 1],
            (word & (ROW_WORDS - 1))[:, None], bits=(2, 3, 4, 5, 6))
        meta = _window_to_lane0(
            lane, meta_piece[mq], meta_piece[mq + 1],
            (at & (ROW_WORDS - 1))[:, None], bits=range(7), shift=_META_AT)
        row = jnp.where(meta_lane, _spread(meta, _META_SPREAD),
                        _spread(keys, _KEY_SPREAD))
        row = jnp.where(lane < 5 * f[:, None], row, 0)
        row = jnp.where(lane == FILL_WORD, f[:, None].astype(jnp.uint32), row)
        old = jax.lax.dynamic_slice(rows, (b0, 0), (block, ROW_WORDS))
        return jax.lax.dynamic_update_slice(
            rows, jnp.where(mine[:, None], row, old), (b0, 0))

    return jax.lax.fori_loop(lo // block, (hi + block - 1) // block,
                             build, rows)


def packed_pieces(fill: np.ndarray, keys: np.ndarray, meta: np.ndarray,
                  piece: int):
    """``(base int32[nb], pieces)`` of a packed base for
    :func:`unpack_rows`, on the host, or :func:`packed_ends`'
    ``ValueError``: ``pieces`` yields ``(start, lo, hi, keys_piece,
    meta_piece)``, the stream cut every ``piece`` rows.
    A piece is a view of ``keys`` / ``meta`` (no copy) but for the
    stream's end, which is padded with zeros. Every bucket's first row
    lies in exactly one piece (an empty bucket's is the next bucket's;
    past the last row, the row count's own piece), so the pieces
    together write every bucket."""
    nb, rows = fill.shape[0], keys.shape[0]
    base = packed_ends(fill, keys, meta) - fill
    starts = np.arange(rows // piece + 1, dtype=np.int64) * piece
    cuts = np.append(np.searchsorted(base, starts, side="left"), nb)
    span = piece + UNPACK_HALO

    def window(flat, first, length):
        part = flat[first:first + length]
        if part.shape[0] < length:
            part = np.concatenate(
                [part, np.zeros(length - part.shape[0], flat.dtype)])
        return part.reshape(-1, ROW_WORDS)

    def pieces():
        kflat, mflat = keys.reshape(-1), meta.reshape(-1)
        for i, start in enumerate(starts):
            yield (np.int32(start), np.int32(cuts[i]), np.int32(cuts[i + 1]),
                   window(kflat, 4 * start, 4 * span),
                   window(mflat, start, span))

    return base.astype(np.int32), pieces()


# -- growth on the device ----------------------------------------------------
#
# ``_home_bucket`` is ``h & (n_buckets - 1)``: doubling the buckets
# splits bucket ``b`` into ``b`` and ``b + n_buckets`` by one more bit
# of ``h``, and a row that lies in its home bucket goes to one of the
# two whatever else the table holds. So a table is grown by ONE
# streaming pass over the old rows (a block of buckets at a time: read
# a row, write two, each compacted to the front and with its fill
# word), and only the rows that lay PAST a full bucket (~0.5% at load
# 0.70) are inserted the ordinary way afterwards, a chunk at a time,
# found among the old rows by the search index a packed save uses.
# Every shape follows the two capacities; nothing follows the row
# count, and no row leaves the device.
#
# The layout rule of the insert holds here too, and more strictly:
# every intermediate is a full [B, 128] row or a [B] vector made by a
# row reduction, and NO column is sliced out of a row. Lane ``l`` of a
# row is word ``l % 5`` of slot ``l // 5``, so a lane roll by one puts
# every slot's second word under its first (all 24 hashes in one
# multiply and XOR), one running sum along the lanes ranks every slot
# among its class (:func:`_slot_classes`), and the word-parallel
# compress moves the kept slots to the front (:func:`_compact`). The
# split is a Pallas kernel a block of buckets (:func:`_split_kernel`):
# ``pltpu.roll`` is the chip's lane rotate, and a block is dealt a
# tile of rows at a time, so that the chain's intermediates stay a
# tile's and only the block and its two halves cross to HBM.
#
# MEASURED (PR 49, one v5e, the split alone on 2^22 buckets at load
# 0.69, every candidate's rows equal to the first's word for word):
#   the select chain (PR 48)                              0.728 s
#   this arithmetic under XLA (``jnp.roll``, a ``fori_loop``
#     over blocks of 8,192 buckets)                       0.110 s
#   this arithmetic as the Pallas kernel, 1,024-bucket blocks dealt
#     8 / 16 / 32 / 64 / 128 / 256 / 512 / 1,024 rows at a time
#       0.560 / 0.283 / 0.150 / 0.078 / 0.045 / 0.036 / 0.032 / 0.033 s
# The faster one ships. The block's size moves nothing (128 .. 4,096
# buckets read the same at a given tile); the rows dealt at a time do:
# the chain is one dependent operation after the other, so its speed
# is the number of independent registers each operation has in flight,
# up to 512 rows (0.0315 s at 512-bucket blocks dealt whole: shipped).
# Inside ``jit_grow_rehash`` the select chain took 1.03 s (6.3 GB/s,
# 0.58% of what a copy of the same bytes takes: the [B] columns it
# sliced out of a [B, 128] block, 120 lane-to-sublane extractions a
# block, and the five-deep ``where`` that put each back over a
# [B, 256] row cost far more than their count) and the program 1.346 s;
# it now takes 0.32 s, 0.27 of it the six ordinary inserts of the
# rows that lay past a full bucket.

#: Old buckets one step of the split's grid takes.
SPLIT_BLOCK = 512
#: Rows of it the kernel deals at a time (the probe: all of them).
SPLIT_TILE = 512
#: Rows of one ordinary insert of rows that lay past a full bucket.
REHOME_CHUNK = 1 << 16

# A slot's class, written over its five lanes; the three share a word
# so that one running sum along the lanes counts all of them (a count
# is at most 24, a field has five bits).
_LO, _HI, _AWAY = 0, 5, 10


def _left(x: jax.Array, k: int) -> jax.Array:
    """``x`` with every row rolled ``k`` lanes to the left, wrapping:
    lane ``l`` takes lane ``l + k``. The XLA spelling; the split's
    kernel passes the chip's lane rotate in its place."""
    return jnp.roll(x, -k, axis=1)


def _slot_classes(blk: jax.Array, bucket: jax.Array, nb: int, left=_left):
    """``(cls, run, lane, slot)`` of bucket rows ``blk`` (numbered
    ``bucket``, which broadcasts against them, in a table of ``nb``),
    every one a full row, all 24 slots at once.

    ``cls`` holds, on each of a slot's five lanes, ``1 << _LO`` where
    the slot's row lies in its home bucket and the doubled table keeps
    it in bucket ``b``, ``1 << _HI`` where it goes to ``b + nb``,
    ``1 << _AWAY`` where the row lies past its home, 0 where the slot
    is empty (and on words 120..127). ``run`` is the running sum of
    ``cls`` over the slots up to and including a lane's own, so
    ``run - cls`` is a slot's rank among its class, and from lane 120
    on ``run`` is the row's three totals."""
    lane = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
    slot = (lane * 52429) >> 18  # lane // 5, for a lane under 2^14
    nxt = left(blk, 1)
    # At a slot's first lane: its key's hash, and the OR of its four
    # key words (lanes l .. l+3).
    h = blk ^ (nxt * np.uint32(0x9E3779B9))
    pair = blk | nxt
    occupied = (pair | left(pair, 2)) != 0
    home = jax.lax.bitcast_convert_type(
        h & np.uint32(nb - 1), jnp.int32) == bucket
    upper = (h & np.uint32(nb)) != 0
    first = jnp.where(
        occupied & (lane == slot * 5) & (lane < SLOTS * 5),
        jnp.where(home, jnp.where(upper, 1 << _HI, 1 << _LO), 1 << _AWAY),
        0)
    two = first | left(first, ROW_WORDS - 1)
    cls = two | left(two, ROW_WORDS - 2) | left(first, ROW_WORDS - 4)
    run = cls
    for k in (5, 10, 20, 40, 80):
        run = run + jnp.where(lane >= k, left(run, ROW_WORDS - k), 0)
    return cls, run, lane, slot


def _compact(blk: jax.Array, keep: jax.Array, dist: jax.Array, left=_left):
    """The rows ``blk`` with every kept slot moved ``dist`` slots to
    the left and every other word zero. ``dist`` never falls along a
    row (it is a slot's number less its rank among the kept), so the
    word-parallel compress holds: five conditional rolls by 1, 2, 4, 8
    and 16 slots, lowest bit first, the distance carried with the
    data; two kept slots never meet, and none wraps."""
    data = jnp.where(keep, blk, 0)
    dist = jnp.where(keep, dist, 0)
    for bit in range(5):
        came, came_dist = left(data, 5 << bit), left(dist, 5 << bit)
        arrives = (came_dist & (1 << bit)) != 0
        leaves = (dist & (1 << bit)) != 0
        data = jnp.where(arrives, came, jnp.where(leaves, 0, data))
        dist = jnp.where(arrives, came_dist, jnp.where(leaves, 0, dist))
    return data


def _deal(blk: jax.Array, bucket: jax.Array, nb: int, left=_left):
    """``(lo, hi, past)`` of old bucket rows ``blk``: the rows of each
    that lie in their home bucket, dealt to the two buckets the doubled
    table has for it (in slot order, compacted to the front, fill word
    set, every other word zero), and, on every lane, how many of its
    rows lie past their home (they stay behind for
    :func:`past_home_chunk`)."""
    cls, run, lane, slot = _slot_classes(blk, bucket, nb, left)
    before = run - cls
    halves = []
    for field in (_LO, _HI):
        rows = _compact(blk, ((cls >> field) & 1) != 0,
                        slot - ((before >> field) & 31), left)
        fill = jax.lax.bitcast_convert_type((run >> field) & 31, jnp.uint32)
        halves.append(jnp.where(lane == FILL_WORD, fill, rows))
    return halves[0], halves[1], run >> _AWAY


def split_runs_compiled() -> bool:
    """Whether the split's kernel is compiled for the chip (Mosaic) or
    interpreted, which is what every other backend gets: the same
    arithmetic, row for row."""
    return jax.default_backend() == "tpu"


def _split_kernel(rows_ref, new_ref, past_ref, away_ref, *, nb: int,
                  tile: int):
    """One block of old buckets: ``rows_ref`` uint32[B, 128] in,
    ``new_ref`` uint32[2, B, 128] (the block's buckets and their upper
    twins) and ``past_ref`` int32[1, 1, B] out. The block is dealt
    ``tile`` rows at a time, so that every intermediate of
    :func:`_deal` is a tile's and none is the block's; ``away_ref``
    float32[B, 128] keeps each row's count of rows past home (on lanes
    120..127) until the block's counts are turned onto the lanes in
    one product."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block = rows_ref.shape[0]
    first = pl.program_id(0) * block

    def left(x, k):
        return pltpu.roll(x, (ROW_WORDS - k) % ROW_WORDS, 1)

    def deal_tile(t, carry):
        at = pl.multiple_of(t * tile, tile)
        bucket = (first + at) + jax.lax.broadcasted_iota(
            jnp.int32, (tile, ROW_WORDS), 0)
        lo, hi, away = _deal(rows_ref[pl.ds(at, tile), :], bucket, nb, left)
        new_ref[0, pl.ds(at, tile), :] = lo
        new_ref[1, pl.ds(at, tile), :] = hi
        away_ref[pl.ds(at, tile), :] = away.astype(jnp.float32)
        return carry

    jax.lax.fori_loop(0, block // tile, deal_tile, 0)
    # past[b] = away[b, 120]: a [8, 128] x [B, 128]^T product with ones
    # on lane 120 (counts up to 24: exact in bf16, summed in f32).
    pick = (jax.lax.broadcasted_iota(jnp.int32, (8, ROW_WORDS), 1)
            == FILL_WORD).astype(jnp.bfloat16)
    turned = jax.lax.dot_general(
        pick, away_ref[...].astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    past_ref[0] = turned[:1].astype(jnp.int32)


def split_rows(rows: jax.Array):
    """``(rows of the doubled table, past int32[nb])``: every row that
    lies in its home bucket, in the bucket the doubled table has for
    it; per old bucket, how many rows were left behind. One Pallas
    kernel over blocks of ``SPLIT_BLOCK`` old buckets: a block is read
    once and its two halves written once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nb = rows.shape[0]
    block = min(nb, SPLIT_BLOCK)
    new_rows, past = pl.pallas_call(
        functools.partial(_split_kernel, nb=nb, tile=min(block, SPLIT_TILE)),
        grid=(nb // block,),
        in_specs=[pl.BlockSpec((block, ROW_WORDS), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((2, block, ROW_WORDS), lambda i: (0, i, 0)),
            pl.BlockSpec((1, 1, block), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((2, nb, ROW_WORDS), jnp.uint32),
            jax.ShapeDtypeStruct((nb // block, 1, block), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((block, ROW_WORDS), jnp.float32)],
        interpret=not split_runs_compiled(),
    )(rows)
    return new_rows.reshape(2 * nb, ROW_WORDS), past.reshape(nb)


def past_home_chunk(rows: jax.Array, index, start: jax.Array, chunk: int):
    """``(keys uint32[chunk, 4], meta uint32[chunk], valid bool[chunk])``:
    rows ``start .. start+chunk``, in bucket order, of those that lie
    past their home bucket; ``index`` is :func:`_running_index` of
    :func:`split_rows`' ``past``. The bucket is found as
    :func:`pack_chunk` finds it; the slot is the bucket's ``j - base``-th
    that holds such a row: one equality on the slots' ranks among them
    and five masked lane sums."""
    nb = rows.shape[0]
    j = start + jnp.arange(chunk, dtype=jnp.int32)
    g, base = _locate(index, j)
    g = jnp.minimum(g, nb - 1)
    blk = rows[g]  # [chunk, 128]
    cls, run, lane, slot = _slot_classes(blk, g[:, None], nb)
    wanted = (((cls >> _AWAY) != 0)
              & (((run - cls) >> _AWAY) == (j - base)[:, None]))
    word = lane - slot * 5
    picked = [jnp.sum(jnp.where(wanted & (word == i), blk, 0), axis=1,
                      dtype=jnp.uint32) for i in range(5)]
    return jnp.stack(picked[:4], axis=1), picked[4], j < index[-1][-1]


@functools.partial(jax.jit, static_argnames=("max_probes",))
def grow_rehash(state: BucketTable, max_probes: int = 32):
    """The table at twice the buckets: ``(new_state, rehomed int32[],
    overflowed int32[])``, one program (XLA module ``jit_grow_rehash``:
    the benchmark's readers find it by that name). ``rehomed`` rows lay
    past a full bucket and were inserted the ordinary way (which keeps
    the lookup invariant: every other row lies in its home bucket);
    ``overflowed`` of them found no room within ``max_probes`` hops and
    are NOT in the new table: the caller keeps the old one, which this
    does not donate."""
    new_rows, past = split_rows(state.rows)
    index = _running_index(past)
    rehomed = index[-1][-1]
    table = BucketTable(new_rows, state.count - rehomed)

    def cond(carry):
        return carry[0] * REHOME_CHUNK < rehomed

    def body(carry):
        i, table, ovf = carry
        keys, meta, valid = past_home_chunk(
            state.rows, index, i * REHOME_CHUNK, REHOME_CHUNK)
        table, _wu, o = insert(table, keys, meta, valid,
                               max_probes=max_probes)
        return i + 1, table, ovf + jnp.sum(o, dtype=jnp.int32)

    _, table, ovf = jax.lax.while_loop(
        cond, body, (jnp.int32(0), table, jnp.int32(0)))
    return table, rehomed, ovf
