"""The fused device ingest step: the reference's hot loop #2 as one op.

One jitted call does what ``insertCTWorker`` + ``FilesystemDatabase.Store``
do per certificate (/root/reference/cmd/ct-fetch/ct-fetch.go:180-246,
/root/reference/storage/filesystemdatabase.go:158-211), for a whole
batch at once and with no per-entry host round trips:

  parse DER → filter (CA / expired / issuer-CN prefix,
  /root/reference/cmd/ct-fetch/ct-fetch.go:44-70) → gather serial →
  build fingerprint block → SHA-256 → dedup-table insert-if-absent →
  per-issuer new-cert counts.

Lanes the device cannot handle exactly (parse failure, oversized
serial, meta-range overflow, probe overflow) come back in
``host_lane`` and are re-processed by the exact host path — the same
tolerate-and-redirect contract the reference applies to unparseable
entries (/root/reference/cmd/ct-fetch/ct-fetch.go:206-225).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ct_mapreduce_tpu.core import packing
from ct_mapreduce_tpu.ops import buckettable, der_kernel, hashtable, sha256


def table_layout() -> str:
    """Dedup-table layout: ``bucket`` (default — the sort-based
    24-slot-bucket table the round-4 hardware measurements favor by
    ~an order of magnitude on the insert, ops/buckettable.py) or
    ``open`` (slot-granular open addressing, ops/hashtable.py)."""
    import os

    layout = os.environ.get("CTMR_TABLE", "bucket").strip().lower()
    if layout not in ("bucket", "open"):
        import warnings

        warnings.warn(
            f"ignoring CTMR_TABLE={layout!r} (want bucket|open); "
            "using bucket", stacklevel=2)
        return "bucket"
    return layout


def make_table(capacity: int, layout: str | None = None):
    """Fresh dedup table in the selected layout."""
    if (layout or table_layout()) == "bucket":
        return buckettable.make_table(capacity)
    return hashtable.make_table(capacity)


def table_insert(table, keys, meta, valid, max_probes: int = 32):
    """Insert-if-absent on either dedup-table layout.

    Dispatches on the state type at trace time (each layout is its own
    pytree, so jit caches separate programs): ``BucketTable`` takes the
    sort-based bucket path (ops/buckettable.py — the measured-fast
    layout), ``hashtable.TableState`` the slot-granular probe path."""
    if isinstance(table, buckettable.BucketTable):
        return buckettable.insert(table, keys, meta, valid,
                                  max_probes=max_probes)
    return hashtable.insert(table, keys, meta, valid, max_probes=max_probes)


class StepOut(NamedTuple):
    was_unknown: jax.Array  # bool[B] — device-confirmed first sighting
    host_lane: jax.Array  # bool[B] — lane needs the exact host path
    filtered_ca: jax.Array  # bool[B]
    filtered_expired: jax.Array  # bool[B]
    filtered_cn: jax.Array  # bool[B]
    stored: jax.Array  # bool[B] — passed filters, device-handled
    not_after_hour: jax.Array  # int32[B]
    serials: jax.Array  # uint8[B, MAX_SERIAL_BYTES] (for PEM/host use)
    serial_len: jax.Array  # int32[B]
    issuer_unknown_counts: jax.Array  # int32[num_issuers]
    has_crldp: jax.Array  # bool[B]
    crldp_off: jax.Array  # int32[B] — CRLDP extnValue window in `data`
    crldp_len: jax.Array  # int32[B]
    issuer_name_off: jax.Array  # int32[B] — issuer Name TLV window
    issuer_name_len: jax.Array  # int32[B]
    probe_overflow: jax.Array  # bool[B] — insert exhausted its probe
    # chain (spills to the exact host lane; `overflow` metric)
    # bool[B] under a CN filter, None without one (LocalLanes has both).
    cn_passed: Optional[jax.Array] = None
    cn_undecidable: Optional[jax.Array] = None


def fingerprints(
    issuer_idx: jax.Array, exp_hour: jax.Array, serials: jax.Array, serial_len: jax.Array
) -> jax.Array:
    """Build fingerprint blocks on device and hash them: uint32[B, 4].

    Message layout must match
    :func:`ct_mapreduce_tpu.core.packing.fingerprint_message`.
    """
    b = issuer_idx.shape[0]
    msg = jnp.zeros((b, 64), dtype=jnp.uint8)
    eh = exp_hour.astype(jnp.uint32)
    ii = issuer_idx.astype(jnp.uint32)
    head = jnp.stack(
        [
            (eh >> 24) & 0xFF, (eh >> 16) & 0xFF, (eh >> 8) & 0xFF, eh & 0xFF,
            (ii >> 24) & 0xFF, (ii >> 16) & 0xFF, (ii >> 8) & 0xFF, ii & 0xFF,
            serial_len.astype(jnp.uint32) & 0xFF,
        ],
        axis=1,
    ).astype(jnp.uint8)
    msg = msg.at[:, :9].set(head)
    msg = msg.at[:, 9 : 9 + packing.MAX_SERIAL_BYTES].set(serials)
    # FIPS padding: 0x80 right after the message, bit length in the
    # last two bytes (messages are < 2^13 bits).
    msg_len = 9 + serial_len
    pos = jnp.arange(64, dtype=jnp.int32)[None, :]
    msg = jnp.where(pos == msg_len[:, None], jnp.uint8(0x80), msg)
    bits = (msg_len * 8).astype(jnp.uint32)
    msg = msg.at[:, 62].set(((bits >> 8) & 0xFF).astype(jnp.uint8))
    msg = msg.at[:, 63].set((bits & 0xFF).astype(jnp.uint8))
    words = msg.reshape(b, 16, 4).astype(jnp.uint32)
    block = (
        (words[:, :, 0] << 24) | (words[:, :, 1] << 16)
        | (words[:, :, 2] << 8) | words[:, :, 3]
    )
    return sha256.sha256_fingerprint64(block)


def _cn_prefix_match(
    rows, cn_off: jax.Array, cn_len: jax.Array,
    prefixes: jax.Array, prefix_lens: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Does the issuer CN start with any configured prefix?

    prefixes: uint8[P, K], the first K bytes of each prefix; prefix_lens:
    int32[P, 2]: column 0 the device-comparable length (= min(len, K)),
    column 1 the TRUE configured length, both -1 on a DEAD row, which
    matches nothing. ``TpuAggregator.set_cn_prefixes`` builds both at
    one shape whatever the directive says (P a fixed number of rows, K =
    der_kernel.MAX_FIXED_WINDOW_BYTES, unused rows dead), so that the
    step is one program under every filter. A live row of length 0 (an
    empty element of the directive) matches every name, as
    ``strings.HasPrefix(name, "")`` does. P == 0 is handled by the
    caller (filter disabled). ``rows`` are the shared word-packed rows
    (:func:`der_kernel.window_bytes_rows` — gather-free).

    Returns ``(hit, undecidable)`` bool[B]: ``hit`` = definitely
    matches some prefix; ``undecidable`` = no hit, and either the name
    matches the K-byte head of a LONGER-than-K prefix and is long
    enough that the tail could match, or the scan could not say what
    the CommonName is (``cn_len`` -1, :func:`der_kernel._scan_issuer_cn`).
    The device cannot decide such a lane, so it takes the exact host
    lane (the reference compares full prefixes with the full name,
    /root/reference/cmd/ct-fetch/ct-fetch.go:56-62).
    """
    k = prefixes.shape[1]
    unsaid = cn_len < 0
    cn_len = jnp.maximum(cn_len, 0)
    window = der_kernel.window_bytes_rows(rows, cn_off, k).astype(jnp.uint8)
    inside = jnp.arange(k, dtype=jnp.int32)[None, :] < cn_len[:, None]
    window = jnp.where(inside, window, 0)
    dev_lens = prefix_lens[:, 0]
    true_lens = prefix_lens[:, 1]
    live = (dev_lens >= 0)[None, :]
    # [B, P, K] compare, masked beyond each prefix's device length
    eq = window[:, None, :] == prefixes[None, :, :]
    care = jnp.arange(k, dtype=jnp.int32)[None, None, :] < dev_lens[None, :, None]
    full = jnp.all(eq | ~care, axis=-1) & live  # [B, P]
    truncated = (true_lens > dev_lens)[None, :]
    # An unsaid name compares as the empty one: only an empty prefix,
    # which every name starts with, hits it.
    hit = jnp.any(
        full & (cn_len[:, None] >= dev_lens[None, :]) & ~truncated, axis=-1
    )
    undecidable = ~hit & (unsaid | jnp.any(
        full & (cn_len[:, None] >= true_lens[None, :]) & truncated, axis=-1
    ))
    return hit, undecidable


class LocalLanes(NamedTuple):
    """Per-lane results of the communication-free ingest stages."""

    parsed: "der_kernel.ParsedCerts"
    serials: jax.Array  # uint8[B, MAX_SERIAL_BYTES]
    filtered_ca: jax.Array
    filtered_expired: jax.Array
    filtered_cn: jax.Array
    # What the CN predicate said of the lanes that reached it; None
    # where no filter is configured (the trace is then what it was).
    cn_passed: Optional[jax.Array]  # the name starts with a prefix
    cn_undecidable: Optional[jax.Array]  # the host lane's to decide
    passed: jax.Array  # survived all filters
    device_exact: jax.Array  # serial/meta/issuer fit the device schema
    insertable: jax.Array  # passed & device_exact
    fps: jax.Array  # uint32[B, 4] dedup fingerprints
    meta: jax.Array  # uint32[B] packed (issuer_idx, exp-hour offset)


def local_lanes(
    data: jax.Array,
    length: jax.Array,
    issuer_idx: jax.Array,
    valid: jax.Array,
    now_hour: jax.Array,
    base_hour: jax.Array,
    cn_prefixes: jax.Array,
    cn_prefix_lens: jax.Array,
    num_issuers: int,
) -> LocalLanes:
    """Parse → filter → fingerprint, shared by the single-chip step and
    the per-device body of the mesh-sharded step (no communication).

    Rows are word-packed ONCE and shared by the parse walker, the
    serial extraction, and the CN window — one pass over [B, L], not
    three (der_kernel's gather-free access path)."""
    rows = der_kernel.pack_rows(data)
    # The RDN scan only feeds the CN-prefix filter; with no prefixes
    # configured (static shape) it is dead work — skip it at trace time.
    parsed = der_kernel.parse_certs_rows(
        rows, length, scan_issuer_cn=cn_prefixes.shape[0] > 0
    )
    ok = parsed.ok & valid

    serials, fits = der_kernel.gather_serials_rows(
        rows, parsed.serial_off, parsed.serial_len, packing.MAX_SERIAL_BYTES
    )

    # Filters, in the reference's precedence order
    # (/root/reference/cmd/ct-fetch/ct-fetch.go:44-70).
    f_ca = ok & parsed.is_ca
    f_expired = ok & ~f_ca & (parsed.not_after_hour < now_hour)
    if cn_prefixes.shape[0] > 0:
        cn_hit, cn_undec = _cn_prefix_match(
            rows, parsed.issuer_cn_off, parsed.issuer_cn_len,
            cn_prefixes, cn_prefix_lens,
        )
        # A lane matching only the truncated head of an over-long
        # prefix, or whose Name the scan could not read as Go does, is
        # NOT filtered here — it routes to the exact host lane below
        # (device_exact), where the full parse and full prefixes decide.
        reached = ok & ~f_ca & ~f_expired
        cn_passed = reached & cn_hit
        cn_undec = reached & ~cn_hit & cn_undec
        f_cn = reached & ~cn_hit & ~cn_undec
    else:
        f_cn = cn_undec = jnp.zeros_like(ok)
        cn_passed = None
    passed = ok & ~f_ca & ~f_expired & ~f_cn

    # Device-exactness gate: lanes outside the packed schema go host-side.
    # A cert expiring WITHIN the current hour is also routed to the
    # exact host lane: the device compares hour buckets, the reference
    # compares instants (`NotAfter.Before(now)`,
    # /root/reference/cmd/ct-fetch/ct-fetch.go:52-55); buckets strictly
    # before/after `now_hour` classify identically either way, and the
    # boundary bucket gets the exact instant compare on host
    # (TpuAggregator._host_exact), so the combined system matches the
    # reference exactly.
    hour_off = parsed.not_after_hour - base_hour
    meta_ok = (hour_off >= 0) & (hour_off < packing.META_HOUR_SPAN)
    idx_ok = (issuer_idx >= 0) & (issuer_idx < num_issuers)
    boundary_hour = parsed.not_after_hour == now_hour
    device_exact = fits & meta_ok & idx_ok & ~boundary_hour & ~cn_undec

    fps = fingerprints(issuer_idx, parsed.not_after_hour, serials, parsed.serial_len)
    meta = (
        (issuer_idx.astype(jnp.uint32) << packing.META_HOUR_BITS)
        | jnp.clip(hour_off, 0, packing.META_HOUR_SPAN - 1).astype(jnp.uint32)
    )
    return LocalLanes(
        parsed=parsed,
        serials=serials,
        filtered_ca=f_ca,
        filtered_expired=f_expired,
        filtered_cn=f_cn,
        cn_passed=cn_passed,
        cn_undecidable=None if cn_passed is None else cn_undec,
        passed=passed,
        device_exact=device_exact,
        insertable=passed & device_exact,
        fps=fps,
        meta=meta,
    )


def ingest_core(
    table: hashtable.TableState,
    data: jax.Array,
    length: jax.Array,
    issuer_idx: jax.Array,
    valid: jax.Array,
    now_hour: jax.Array,
    base_hour: jax.Array,
    cn_prefixes: jax.Array,
    cn_prefix_lens: jax.Array,
    num_issuers: int = packing.MAX_ISSUERS,
    max_probes: int = 32,
) -> tuple[hashtable.TableState, StepOut]:
    """Process one packed batch end-to-end on device.

    Args:
      table: dedup state (donated).
      data/length/issuer_idx/valid: the packed batch.
      now_hour: scalar int32 — "now" for the expiry filter (the
        reference filters ``NotAfter.Before(now)``).
      base_hour: scalar int32 — meta-word epoch base.
      cn_prefixes/cn_prefix_lens: uint8[P, K]/int32[P, 2]
        (device-comparable length, true length; -1 on a dead row);
        P == 0 disables the CN filter. The shapes are static, and
        ``TpuAggregator.set_cn_prefixes`` gives every filter the same
        ones: the step has two programs a batch shape in all, filter
        off and filter on, whatever the directive says.
    """
    lanes = local_lanes(
        data, length, issuer_idx, valid, now_hour, base_hour,
        cn_prefixes, cn_prefix_lens, num_issuers,
    )
    parsed = lanes.parsed

    table, was_unknown, overflowed = table_insert(
        table, lanes.fps, lanes.meta, lanes.insertable, max_probes=max_probes
    )

    host_lane = (
        (valid & ~parsed.ok) | (lanes.passed & ~lanes.device_exact) | overflowed
    )

    issuer_counts = jnp.zeros((num_issuers,), jnp.int32).at[issuer_idx].add(
        was_unknown.astype(jnp.int32), mode="drop"
    )

    return table, StepOut(
        was_unknown=was_unknown,
        host_lane=host_lane,
        probe_overflow=overflowed,
        filtered_ca=lanes.filtered_ca,
        filtered_expired=lanes.filtered_expired,
        filtered_cn=lanes.filtered_cn,
        stored=lanes.insertable & ~overflowed,
        not_after_hour=parsed.not_after_hour,
        serials=lanes.serials,
        serial_len=parsed.serial_len,
        issuer_unknown_counts=issuer_counts,
        has_crldp=parsed.has_crldp,
        crldp_off=parsed.crldp_off,
        crldp_len=parsed.crldp_len,
        issuer_name_off=parsed.issuer_off,
        issuer_name_len=parsed.issuer_len,
        cn_passed=lanes.cn_passed,
        cn_undecidable=lanes.cn_undecidable,
    )


# -- pre-parsed ingest lane ---------------------------------------------
#
# When the native decoder has already extracted the identity fields on
# the host (native/ctmr_native.cpp ctmr_extract_sidecars — a scalar
# port of the device walker), the device step collapses to its
# arithmetic floor: fingerprint SHA-256 + dedup-table insert +
# per-issuer counts. No row bytes ship to the device at all (~59 B of
# compact inputs per lane instead of 1-2 KB of padded DER), the
# word-pack and the DER walker (~107 of the walker step's ~194
# ns/entry per the round-5 cost model) disappear, and the readback is
# COMPACT: a was-unknown bitmask (1 bit/lane), sort-compacted
# probe-overflow lane indices (O(flagged), not O(batch)), and the
# count vectors — packed into ONE int32 array so a dispatch costs one
# D2H read.
#
# Every filter/routing predicate that doesn't depend on table state
# (CA/expired/CN filters, the device-exactness gates) is a pure
# function of the sidecar and is evaluated by the HOST
# (agg/aggregator.py ingest_preparsed_submit) with arithmetic
# mirroring local_lanes exactly; only `insertable` reaches the device.

N_PREPARSED_FLAG_CAP = 1024  # default compacted-overflow capacity


class PreparsedStepOut(NamedTuple):
    """Device outputs of the pre-parsed step, readback-oriented."""

    packed: jax.Array  # int32[K, 2 + nb + flag_cap + num_issuers] — the
    # ONE array the host reads per dispatch; per chunk row:
    #   [0] unknown_count, [1] overflow_count,
    #   [2 : 2+nb] was-unknown bitmask (bit i of word w = lane w*32+i),
    #   [2+nb : 2+nb+flag_cap] overflow lane ids ascending (B = none),
    #   [2+nb+flag_cap :] per-issuer fresh-insert counts.
    overflow_bits: jax.Array  # uint32[K, nb] — full overflow bitmask,
    # fetched ONLY when overflow_count exceeds flag_cap (spill).


def _pack_bits(flags: jax.Array, nb: int) -> jax.Array:
    """bool[B] → uint32[nb] bitmask (bit i of word w = lane w*32+i)."""
    b = flags.shape[0]
    padded = jnp.pad(flags, (0, nb * 32 - b)).reshape(nb, 32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))[None, :]
    return jnp.sum(jnp.where(padded, weights, jnp.uint32(0)), axis=1)


def preparsed_core(
    table,
    serials: jax.Array,  # uint8[K, B, MAX_SERIAL_BYTES]
    serial_len: jax.Array,  # int32[K, B]
    not_after_hour: jax.Array,  # int32[K, B]
    issuer_idx: jax.Array,  # int32[K, B]
    insertable: jax.Array,  # bool[K, B] — host-computed gate
    base_hour: jax.Array,  # int32 scalar
    num_issuers: int = packing.MAX_ISSUERS,
    max_probes: int = 32,
    flag_cap: int = N_PREPARSED_FLAG_CAP,
):
    """Fused multi-chunk pre-parsed step: ONE device execution for K
    resident chunks (fori_loop, like the aggregator's reinsert path) —
    one dispatch and one D2H read instead of K of each."""
    k_chunks, b = serial_len.shape
    nb = -(-b // 32)
    width = 2 + nb + flag_cap + num_issuers
    packed0 = jnp.zeros((k_chunks, width), jnp.int32)
    ovf_bits0 = jnp.zeros((k_chunks, nb), jnp.uint32)

    def body(k, carry):
        table, packed, ovf_bits = carry
        fps = fingerprints(
            issuer_idx[k], not_after_hour[k], serials[k], serial_len[k]
        )
        hour_off = not_after_hour[k] - base_hour
        meta = (
            (issuer_idx[k].astype(jnp.uint32) << packing.META_HOUR_BITS)
            | jnp.clip(hour_off, 0, packing.META_HOUR_SPAN - 1).astype(
                jnp.uint32)
        )
        table, wu, ovf = table_insert(
            table, fps, meta, insertable[k], max_probes=max_probes
        )
        counts = jnp.zeros((num_issuers,), jnp.int32).at[issuer_idx[k]].add(
            wu.astype(jnp.int32), mode="drop"
        )
        iota = jnp.arange(b, dtype=jnp.int32)
        ovf_idx = jnp.sort(jnp.where(ovf, iota, b))[:flag_cap]
        if flag_cap > b:  # tiny chunks: keep the packed width static
            ovf_idx = jnp.pad(ovf_idx, (0, flag_cap - b),
                              constant_values=b)
        row = jnp.concatenate([
            jnp.stack([wu.sum(dtype=jnp.int32), ovf.sum(dtype=jnp.int32)]),
            jax.lax.bitcast_convert_type(_pack_bits(wu, nb), jnp.int32),
            ovf_idx,
            counts,
        ])
        return (
            table,
            packed.at[k].set(row),
            ovf_bits.at[k].set(_pack_bits(ovf, nb)),
        )

    table, packed, ovf_bits = jax.lax.fori_loop(
        0, k_chunks, body, (table, packed0, ovf_bits0)
    )
    return table, PreparsedStepOut(packed=packed, overflow_bits=ovf_bits)


# The production entry point: donated table state, cached per shape.
ingest_step = functools.partial(
    jax.jit,
    static_argnames=("num_issuers", "max_probes"),
    donate_argnums=(0,),
)(ingest_core)

# Pre-parsed lane entry points (donating and not: CPU's XLA can't
# alias the donated layouts and warns per dispatch, so the aggregator
# picks by backend exactly like the walker-lane pair below).
ingest_step_preparsed = functools.partial(
    jax.jit,
    static_argnames=("num_issuers", "max_probes", "flag_cap"),
)(preparsed_core)

ingest_step_preparsed_donated = functools.partial(
    jax.jit,
    static_argnames=("num_issuers", "max_probes", "flag_cap"),
    donate_argnums=(0,),
)(preparsed_core)

# Donates the packed row buffer too: the sink hands the step a
# device-resident batch it will never touch again (host-lane fallbacks
# slice the separate host copy),
# so donating `data` lets XLA reuse ~batch-size HBM per in-flight batch
# instead of holding the input rows live alongside the step's
# intermediates — at deviceQueueDepth 2 that is two full batches of
# headroom. NumPy rows (a chunk short of the batch, the synchronous
# per-entry path) are put on the device by the aggregator and take this
# entry point too, so a row shape is one program; `ingest_step` is the
# CPU backend's, which cannot alias the donated layout.
ingest_step_donated = functools.partial(
    jax.jit,
    static_argnames=("num_issuers", "max_probes"),
    donate_argnums=(0, 1),
)(ingest_core)
