"""Multi-chip sharded dedup: the distributed reduce over a device mesh.

The reference scales out by pointing many processes at one Redis
(/root/reference/coordinator/coordinator.go); the shared SADD state is
the bottleneck every worker serializes on. Here the dedup table is
**sharded by key across the mesh** and batches are **sharded along the
batch axis** (DP), with an expert-parallel-style exchange in between —
the TPU-native layout SURVEY.md §2.2/§2.3 prescribes:

1. Each device parses/filters/fingerprints its local slice of the batch
   (pure data parallelism — no communication).
2. Each fingerprint's *home shard* is a hash of the key; lanes are
   routed to their home with a fixed-capacity dispatch + ``all_to_all``
   over ICI (exactly the MoE token-dispatch pattern, with certificates
   as tokens and table shards as experts).
3. Every device runs the insert-if-absent op against its local table
   shard — keys for one shard never touch another, so no cross-device
   races exist by construction.
4. Results ride the inverse ``all_to_all`` home and are scattered back
   to original lane order.

Dispatch capacity is ``factor × B_local / n_shards`` per
(source, destination) pair; lanes that overflow a full dispatch slot
are flagged and take the exact host lane, identically to probe
overflow — the parity contract never depends on capacity tuning.

Everything is a single ``shard_map``-wrapped jitted step over a 1-D
``jax.sharding.Mesh``; the same code runs on a virtual CPU mesh in
tests and on a TPU pod slice in production.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ct_mapreduce_tpu.core import packing
from ct_mapreduce_tpu.ops import buckettable, hashtable, pipeline

AXIS = "shard"


def mesh_capacity(n_shards: int, capacity: int,
                  layout: str | None = None) -> int:
    """Smallest capacity ≥ ``capacity`` that divides over ``n_shards``
    with a power-of-two per-shard unit (slots for the open layout,
    buckets for the bucket layout — the hash mask requirement)."""
    if (layout or pipeline.table_layout()) == "bucket":
        per_slots = max(1, -(-capacity // n_shards))
        nb_loc = 1 << max(
            0, (per_slots + buckettable.SLOTS - 1) // buckettable.SLOTS - 1
        ).bit_length()
        return n_shards * nb_loc * buckettable.SLOTS
    per = max(1, -(-capacity // n_shards))  # ceil
    return n_shards * (1 << (per - 1).bit_length())


class ShardedStepOut(NamedTuple):
    was_unknown: jax.Array  # bool[B]
    host_lane: jax.Array  # bool[B] (parse/serial/meta/probe/dispatch overflow)
    filtered_ca: jax.Array  # bool[B]
    filtered_expired: jax.Array  # bool[B]
    filtered_cn: jax.Array  # bool[B]
    not_after_hour: jax.Array  # int32[B]
    serials: jax.Array  # uint8[B, MAX_SERIAL]
    serial_len: jax.Array  # int32[B]
    issuer_unknown_counts: jax.Array  # int32[num_issuers] (global, replicated)
    has_crldp: jax.Array
    crldp_off: jax.Array
    crldp_len: jax.Array
    issuer_name_off: jax.Array
    issuer_name_len: jax.Array
    probe_overflow: jax.Array  # bool[B] — shard-local insert exhausted
    # its probe chain (spills to the exact host lane; `overflow` metric)
    dispatch_dropped: jax.Array  # bool[B] — lane spilled past the
    # per-(src,dst) routing cap to the exact host lane (surfaced as the
    # aggregator's `dispatch_spill` metric so routing skew is observable)
    # bool[B] under a CN filter, None without one (pipeline.LocalLanes).
    cn_passed: Optional[jax.Array] = None
    cn_undecidable: Optional[jax.Array] = None


def shard_of_np(keys: np.ndarray, n_shards: int) -> np.ndarray:
    """Host mirror of :func:`_shard_of` (uint32 wraparound arithmetic):
    home shard per fingerprint row ``uint32[n, 4]``. Shared by the
    checkpoint-restore router (`bulk_insert_np`) and the pre-parsed
    lane's host-side routing."""
    k = np.asarray(keys).astype(np.uint32)
    h = k[:, 2] ^ (k[:, 3] * np.uint32(0x85EBCA6B))
    return (h % np.uint32(n_shards)).astype(np.int32)


def _shard_of(keys: jax.Array, n_shards: int) -> jax.Array:
    """Home shard of each fingerprint — independent bits from the slot
    hash so shard routing doesn't correlate with in-shard probing.

    Routing is a function of the WHOLE fingerprint (expHour, issuerID,
    serial): because serials differ per certificate, even a single hot
    issuer (Zipfian reality of CT logs) spreads uniformly over shards —
    spills past the per-(src,dst) cap are binomial-tail events, not
    hot-key events (pinned by test_sharded_zipfian_issuer_skew)."""
    h = keys[:, 2] ^ (keys[:, 3] * np.uint32(0x85EBCA6B))
    return (h % np.uint32(n_shards)).astype(jnp.int32)


def probe_width(lanes: int) -> int:
    """The compiled width of a membership probe of ``lanes`` lanes: the
    next power of two, 16 at least (the one-chip probe's rule)."""
    return max(16, 1 << max(0, (lanes - 1).bit_length()))


def route_to_shards(fps: np.ndarray, n_shards: int):
    """A batch's fingerprints ``uint32[n, 4]`` laid out by home shard
    as ``uint32[n_shards, width, 4]``, and where each lane went
    (``dest``, ``pos``: lane ``i`` is ``[dest[i], pos[i]]``). ``width``
    is :func:`probe_width` of the batch's lane count and of nothing
    else, so it holds the batch whatever its fingerprints hash to (all
    of them to one shard at worst): the shape a probe compiles for
    follows from how many lanes were asked after, never from which."""
    n = fps.shape[0]
    dest = shard_of_np(fps, n_shards)
    order = np.argsort(dest, kind="stable")
    per_shard = np.bincount(dest, minlength=n_shards)
    starts = np.cumsum(per_shard) - per_shard
    pos = np.empty((n,), np.int64)
    pos[order] = np.arange(n) - starts[dest[order]]
    keys = np.zeros((n_shards, probe_width(n), 4), np.uint32)
    keys[dest, pos] = fps
    return keys, dest, pos


@functools.lru_cache(maxsize=None)
def _shard_contains_program(mesh: Mesh, axis: str, layout: str,
                            max_probes: int):
    """The membership probe of a row-sharded table as ONE program over
    the mesh: every shard probes its own row block with its slice of
    the routed keys (the layout's own ``contains``; no collective).
    Jitted once a mesh and layout, compiled once a width; its XLA
    module is ``jit_shard_contains`` (docs/METRICS.md)."""
    if layout == "bucket":
        state_cls, contains = buckettable.BucketTable, buckettable.contains
    else:
        state_cls, contains = hashtable.TableState, hashtable.contains

    def local(block, keys):
        state = state_cls(block, jnp.zeros((), jnp.int32))
        return contains(state, keys[0], max_probes=max_probes)[None]

    def shard_contains(rows, keys):
        return shard_map(local, mesh=mesh, in_specs=(P(axis), P(axis)),
                         out_specs=P(axis), check_vma=False)(rows, keys)

    return jax.jit(shard_contains)


def shard_contains(rows: jax.Array, keys: np.ndarray, layout: str,
                   max_probes: int) -> np.ndarray:
    """bool[n_shards, width]: routed ``keys`` (:func:`route_to_shards`)
    probed against the row-sharded table ``rows``, shard ``i``'s block
    with ``keys[i]`` on shard ``i``'s chip. One placement of the keys,
    one dispatch, one readback, whatever shards the batch touches."""
    split = rows.sharding  # rows and keys alike: block i on chip i
    fn = _shard_contains_program(split.mesh, split.spec[0], layout,
                                 max_probes)
    return np.asarray(fn(rows, jax.device_put(keys, split)))


def _dispatch(
    payload: jax.Array, dest: jax.Array, active: jax.Array,
    n_shards: int, cap: int,
):
    """Route lanes to destination shards with fixed per-dest capacity.

    payload: [B_loc, W] uint32 rows; dest: int32[B_loc]; active: bool.
    Returns (send [n_shards, cap, W], send_valid [n_shards, cap],
    slot_of_lane int32[B_loc] (-1 ⇒ dropped), pos_of_lane int32[B_loc]).
    """
    b = dest.shape[0]
    dest_eff = jnp.where(active, dest, n_shards)  # inactive → dummy bin
    # Rank within destination (MoE position-in-expert). For the usual
    # narrow meshes, one cumsum per shard beats the stable lexsort
    # 3.8x on TPU (1.5 ms vs 5.9 ms at 131K lanes, n=8) and assigns
    # IDENTICAL ranks (both are lane-order-stable). Wide meshes fall
    # back to the sort, whose cost doesn't scale with shard count.
    if n_shards <= 32:
        rank = jnp.zeros((b,), jnp.int32)
        for d in range(n_shards):  # dummy-bin lanes never need a rank
            m = dest_eff == d
            rank = jnp.where(m, jnp.cumsum(m.astype(jnp.int32)) - 1, rank)
    else:
        order = jnp.lexsort((jnp.arange(b, dtype=jnp.int32), dest_eff))
        d_sorted = dest_eff[order]
        is_start = jnp.concatenate(
            [jnp.ones((1,), bool), d_sorted[1:] != d_sorted[:-1]]
        )
        pos = jnp.arange(b, dtype=jnp.int32)
        group_start = jnp.where(is_start, pos, 0)
        group_start = jax.lax.associative_scan(jnp.maximum, group_start)
        rank_sorted = pos - group_start
        rank = jnp.zeros((b,), jnp.int32).at[order].set(rank_sorted)

    fits = active & (rank < cap)
    flat = jnp.where(fits, dest_eff * cap + rank, n_shards * cap)  # OOB drops
    send = jnp.zeros((n_shards * cap, payload.shape[1]), payload.dtype)
    send = send.at[flat].set(payload, mode="drop")
    send_valid = jnp.zeros((n_shards * cap,), bool).at[flat].set(fits, mode="drop")
    return (
        send.reshape(n_shards, cap, payload.shape[1]),
        send_valid.reshape(n_shards, cap),
        jnp.where(fits, flat, -1),
        rank,
    )


def _local_step(
    table_rows, table_count,
    data, length, issuer_idx, valid,
    now_hour, base_hour, cn_prefixes, cn_prefix_lens,
    *, n_shards: int, cap: int, num_issuers: int, max_probes: int,
    bucket: bool = False, axis: str = AXIS,
):
    """Per-device body, run under shard_map over the 1-D mesh."""
    # --- stage 1: local parse / filter / fingerprint (pure DP) ----------
    lanes = pipeline.local_lanes(
        data, length, issuer_idx, valid, now_hour, base_hour,
        cn_prefixes, cn_prefix_lens, num_issuers,
    )
    parsed = lanes.parsed

    # --- stage 2: dispatch to home shards -------------------------------
    # Payload is 5 uint32 words: 4 fingerprint words + the meta word
    # (which already encodes issuer_idx in its high bits).
    dest = _shard_of(lanes.fps, n_shards)
    payload = jnp.concatenate([lanes.fps, lanes.meta[:, None]], axis=1)
    send, send_valid, slot_of_lane, _ = _dispatch(
        payload, dest, lanes.insertable, n_shards, cap
    )
    dispatch_dropped = lanes.insertable & (slot_of_lane < 0)

    recv = jax.lax.all_to_all(send, axis, split_axis=0, concat_axis=0, tiled=True)
    recv_valid = jax.lax.all_to_all(
        send_valid, axis, split_axis=0, concat_axis=0, tiled=True
    )

    # --- stage 3: local insert ------------------------------------------
    rk = recv.reshape(n_shards * cap, 5)
    rvalid = recv_valid.reshape(n_shards * cap)
    rkeys, rmeta = rk[:, :4], rk[:, 4]
    if bucket:
        state = buckettable.BucketTable(table_rows, table_count)
    else:
        state = hashtable.TableState(table_rows, table_count)
    state, r_unknown, r_overflow = pipeline.table_insert(
        state, rkeys, rmeta, rvalid, max_probes=max_probes
    )

    # Per-issuer counts of fresh inserts, reduced across the mesh.
    r_issuer = (rmeta >> packing.META_HOUR_BITS).astype(jnp.int32)
    local_counts = jnp.zeros((num_issuers,), jnp.int32).at[r_issuer].add(
        r_unknown.astype(jnp.int32), mode="drop"
    )
    issuer_counts = jax.lax.psum(local_counts, axis)

    # --- stage 4: route results home (1 word: unknown | overflow<<1) ----
    back = (
        r_unknown.astype(jnp.uint32) | (r_overflow.astype(jnp.uint32) << 1)
    ).reshape(n_shards, cap, 1)
    back = jax.lax.all_to_all(back, axis, split_axis=0, concat_axis=0, tiled=True)
    back = back.reshape(n_shards * cap)

    flat_slot = jnp.where(slot_of_lane >= 0, slot_of_lane, 0)
    lane_res = back[flat_slot]
    sent = slot_of_lane >= 0
    was_unknown = sent & ((lane_res & 1) != 0)
    probe_overflow = sent & ((lane_res & 2) != 0)

    host_lane = (
        (valid & ~parsed.ok)
        | (lanes.passed & ~lanes.device_exact)
        | dispatch_dropped
        | probe_overflow
    )

    return (
        state.rows, state.count,
        ShardedStepOut(
            was_unknown=was_unknown,
            host_lane=host_lane,
            filtered_ca=lanes.filtered_ca,
            filtered_expired=lanes.filtered_expired,
            filtered_cn=lanes.filtered_cn,
            not_after_hour=parsed.not_after_hour,
            serials=lanes.serials,
            serial_len=parsed.serial_len,
            issuer_unknown_counts=issuer_counts,
            has_crldp=parsed.has_crldp,
            crldp_off=parsed.crldp_off,
            crldp_len=parsed.crldp_len,
            issuer_name_off=parsed.issuer_off,
            issuer_name_len=parsed.issuer_len,
            probe_overflow=probe_overflow,
            dispatch_dropped=dispatch_dropped,
            cn_passed=lanes.cn_passed,
            cn_undecidable=lanes.cn_undecidable,
        ),
    )


def _local_preparsed_step(
    table_rows, table_count,
    serials, serial_len, not_after_hour, issuer_idx, insertable,
    base_hour,
    *, num_issuers: int, max_probes: int, flag_cap: int,
    bucket: bool = False, axis: str = AXIS,
):
    """Per-device body of the PRE-PARSED sharded step.

    Lanes arrive ALREADY ROUTED: the host computed every lane's home
    shard from its fingerprint (`core.packing.fingerprints_np` +
    `shard_of_np` — the same hash `_shard_of` uses) and partitioned the
    compact sidecar fields per shard before H2D. So this body is pure
    shard-local work — fingerprint + insert + counts, no dispatch, no
    ``all_to_all`` — and the only collective is the `psum` on the
    per-issuer fresh-insert counts. The ~59 B/lane wire win of the
    pre-parsed lane survives intact (row bytes never ship; the walker
    path would have moved padded rows over the batch axis instead).

    Outputs mirror `pipeline.preparsed_core`'s compact readback, per
    shard: one int32 row [inserted, ovf_count, was-unknown bitmask,
    compacted overflow lane ids] + the full overflow bitmask (fetched
    only on a compacted-flag spill) + replicated psum'd counts.
    """
    c = serial_len.shape[0]  # per-shard lane slots
    nb = -(-c // 32)
    if bucket:
        state = buckettable.BucketTable(table_rows, table_count)
    else:
        state = hashtable.TableState(table_rows, table_count)
    fps = pipeline.fingerprints(issuer_idx, not_after_hour, serials,
                                serial_len)
    hour_off = not_after_hour - base_hour
    meta = (
        (issuer_idx.astype(jnp.uint32) << packing.META_HOUR_BITS)
        | jnp.clip(hour_off, 0, packing.META_HOUR_SPAN - 1).astype(
            jnp.uint32)
    )
    state, wu, ovf = pipeline.table_insert(
        state, fps, meta, insertable, max_probes=max_probes
    )
    local_counts = jnp.zeros((num_issuers,), jnp.int32).at[issuer_idx].add(
        wu.astype(jnp.int32), mode="drop"
    )
    counts = jax.lax.psum(local_counts, axis)
    iota = jnp.arange(c, dtype=jnp.int32)
    ovf_idx = jnp.sort(jnp.where(ovf, iota, c))[:flag_cap]
    if flag_cap > c:
        ovf_idx = jnp.pad(ovf_idx, (0, flag_cap - c), constant_values=c)
    row = jnp.concatenate([
        jnp.stack([wu.sum(dtype=jnp.int32), ovf.sum(dtype=jnp.int32)]),
        jax.lax.bitcast_convert_type(
            pipeline._pack_bits(wu, nb), jnp.int32),
        ovf_idx,
    ])
    return (
        state.rows, state.count,
        row[None],                          # → int32[n_shards, 2+nb+cap]
        pipeline._pack_bits(ovf, nb)[None],  # → uint32[n_shards, nb]
        counts,                              # replicated
    )


class ShardedDedup:
    """Mesh-wide dedup state + the compiled sharded step.

    Table rows are sharded over ``mesh`` axis 0; batches arrive sharded
    along the batch axis. One instance per process (multi-host runs use
    the same global mesh via ``jax.distributed``).
    """

    def __init__(
        self,
        mesh: Mesh,
        capacity: int,
        base_hour: int = packing.DEFAULT_BASE_HOUR,
        num_issuers: int = packing.MAX_ISSUERS,
        max_probes: int = 32,
        dispatch_factor: float = 2.0,
    ) -> None:
        if len(mesh.axis_names) != 1:
            raise ValueError(
                f"ShardedDedup needs a 1-D mesh, got axes {mesh.axis_names}; "
                "flatten the mesh first (models.build_aggregator does this)"
            )
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.n_shards = mesh.devices.size
        self.layout = pipeline.table_layout()
        if capacity % self.n_shards:
            raise ValueError("capacity must divide evenly across the mesh")
        per_shard = capacity // self.n_shards
        # Table rows and batches alike: split along axis 0, block i on
        # chip i.
        row_sharded = self.batch_sharding = NamedSharding(mesh, P(self.axis))
        if self.layout == "bucket":
            # The home-bucket mask operates on each LOCAL shard's
            # bucket array inside shard_map, so per-shard BUCKET count
            # must be a power of two — rounded UP here (capacity is a
            # floor, mirroring buckettable.make_table; the realized
            # slot count is ``self.capacity`` after this block).
            nb_loc = 1 << max(
                0, (per_shard + buckettable.SLOTS - 1) // buckettable.SLOTS
                - 1).bit_length()
            capacity = self.n_shards * nb_loc * buckettable.SLOTS
            # Bucket rows, row-sharded: shard i holds buckets
            # [i*nb_loc, (i+1)*nb_loc).
            self.rows = jnp.zeros(  # made sharded: never whole on one chip
                (self.n_shards * nb_loc, buckettable.ROW_WORDS), jnp.uint32,
                device=row_sharded,
            )
        else:
            # The triangular-probe mask operates on each LOCAL shard
            # inside shard_map, so per-shard SLOT count must be a
            # power of two.
            if per_shard & (per_shard - 1):
                raise ValueError("per-shard capacity must be a power of two")
            # Fused table rows (4 fp words + meta), row-sharded over
            # the mesh — same layout as the single-chip TableState.
            self.rows = jnp.zeros(
                (capacity, 5), jnp.uint32, device=row_sharded
            )
        self.capacity = capacity
        self.base_hour = base_hour
        self.num_issuers = num_issuers
        self.max_probes = max_probes
        self.dispatch_factor = dispatch_factor
        self.count = jnp.zeros(
            (self.n_shards,), jnp.int32, device=row_sharded
        )
        self._step_cache: dict = {}

    def _compiled(self, b: int, l: int, p: int, k: int):
        key = (b, l, p, k)
        fn = self._step_cache.get(key)
        if fn is not None:
            return fn
        n = self.n_shards
        if b % n:
            raise ValueError(f"batch size {b} must divide over {n} shards")
        # Per-(src,dst) dispatch quota: expected b_loc/n with headroom;
        # floored so tiny batches keep full capacity (no spurious
        # host-lane fallbacks in small runs/tests).
        b_loc = b // n
        cap = min(b_loc, max(8, int(self.dispatch_factor * b_loc / n)))

        local = functools.partial(
            _local_step,
            n_shards=n,
            cap=cap,
            num_issuers=self.num_issuers,
            max_probes=self.max_probes,
            bucket=self.layout == "bucket",
            axis=self.axis,
        )
        A = P(self.axis)
        said = A if p else None  # the CN predicate's two, under a filter
        mapped = shard_map(
            local,
            mesh=self.mesh,
            in_specs=(
                A, A,  # fused table rows + per-shard counts
                A, A, A, A,  # batch
                P(), P(), P(), P(),  # scalars + prefixes (replicated)
            ),
            out_specs=(
                A, A,
                ShardedStepOut(
                    was_unknown=A, host_lane=A,
                    filtered_ca=A, filtered_expired=A,
                    filtered_cn=A, not_after_hour=A,
                    serials=A, serial_len=A,
                    issuer_unknown_counts=P(),
                    has_crldp=A, crldp_off=A, crldp_len=A,
                    issuer_name_off=A, issuer_name_len=A,
                    probe_overflow=A, dispatch_dropped=A,
                    cn_passed=said, cn_undecidable=said,
                ),
            ),
            check_vma=False,
        )
        fn = jax.jit(mapped, donate_argnums=(0, 1))
        self._step_cache[key] = fn
        return fn

    def step(
        self,
        data: np.ndarray,
        length: np.ndarray,
        issuer_idx: np.ndarray,
        valid: np.ndarray,
        now_hour: int,
        cn_prefixes: np.ndarray | None = None,
        cn_prefix_lens: np.ndarray | None = None,
    ) -> ShardedStepOut:
        if cn_prefixes is None:
            cn_prefixes = np.zeros((0, 32), np.uint8)
            cn_prefix_lens = np.zeros((0, 2), np.int32)
        b, l = data.shape
        fn = self._compiled(b, l, cn_prefixes.shape[0], cn_prefixes.shape[1])
        self.rows, self.count, out = fn(
            self.rows, self.count,
            *self._place(data, length, issuer_idx, valid),
            np.int32(now_hour), np.int32(self.base_hour),
            cn_prefixes, cn_prefix_lens,
        )
        return out

    def _place(self, *arrays):
        """Each array split along its first axis over the mesh, block i
        on chip i: NumPy goes from the host straight to its chips (never
        through ``jnp.asarray``, which would commit the whole of it to
        the default device first and reshard chip to chip), and an
        array already placed so is handed through untouched."""
        return [jax.device_put(x, self.batch_sharding) for x in arrays]

    def _preparsed_fn(self, c: int, flag_cap: int):
        """Compiled pre-parsed step for per-shard width ``c`` (cached;
        the caller pads c to a power of two so shape churn is log-
        bounded)."""
        key = ("preparsed", c, flag_cap)
        fn = self._step_cache.get(key)
        if fn is not None:
            return fn
        local = functools.partial(
            _local_preparsed_step,
            num_issuers=self.num_issuers,
            max_probes=self.max_probes,
            flag_cap=flag_cap,
            bucket=self.layout == "bucket",
            axis=self.axis,
        )
        A = P(self.axis)
        mapped = shard_map(
            local,
            mesh=self.mesh,
            in_specs=(A, A, A, A, A, A, A, P()),
            out_specs=(A, A, A, A, P()),
            check_vma=False,
        )
        fn = jax.jit(mapped, donate_argnums=(0, 1))
        self._step_cache[key] = fn
        return fn

    def step_preparsed(
        self,
        serials: np.ndarray,      # uint8[n_shards*C, MAX_SERIAL]
        serial_len: np.ndarray,   # int32[n_shards*C]
        not_after_hour: np.ndarray,
        issuer_idx: np.ndarray,
        insertable: np.ndarray,   # bool[n_shards*C]
        flag_cap: int,
    ):
        """Walker-free sharded step over HOST-ROUTED sidecar lanes:
        slot ``s*C + j`` belongs to shard ``s`` (the caller routed each
        lane to ``shard_of_np(fingerprints_np(...))`` and padded every
        shard's range to C with insertable=False slots). Returns
        ``(packed, overflow_bits, counts)`` device arrays — the
        per-shard compact readback of `_local_preparsed_step`."""
        ns = self.n_shards
        c = int(serial_len.shape[0]) // ns
        fn = self._preparsed_fn(c, flag_cap)
        self.rows, self.count, packed, ovf_bits, counts = fn(
            self.rows, self.count,
            *self._place(serials, serial_len, not_after_hour,
                         issuer_idx, insertable),
            np.int32(self.base_hour),
        )
        return packed, ovf_bits, counts

    def _bulk_insert_fn(self, width: int):
        cache_key = ("bulk", width)
        fn = self._step_cache.get(cache_key)
        if fn is not None:
            return fn

        bucket = self.layout == "bucket"

        def local(table_rows, table_count, send, meta, valid):
            if bucket:
                state = buckettable.BucketTable(table_rows, table_count)
            else:
                state = hashtable.TableState(table_rows, table_count)
            state, _, overflow = pipeline.table_insert(
                state, send[0], meta[0], valid[0], max_probes=self.max_probes
            )
            return (
                state.rows, state.count,
                jnp.sum(overflow, dtype=jnp.int32)[None],
            )

        mapped = shard_map(
            local,
            mesh=self.mesh,
            in_specs=tuple([P(self.axis)] * 5),
            out_specs=tuple([P(self.axis)] * 3),
            check_vma=False,
        )
        fn = jax.jit(mapped, donate_argnums=(0, 1))
        self._step_cache[cache_key] = fn
        return fn

    def bulk_insert_np(
        self, keys_np: np.ndarray, meta_np: np.ndarray, chunk: int = 65536
    ) -> int:
        """Reinsert pre-hashed (fingerprint, meta) rows — the
        topology-independent restore path. Rows are routed to their home
        shard on the host (this runs once per restore, not per batch),
        then inserted per-shard under shard_map. Returns the number of
        rows that overflowed probing (0 unless the table is undersized)."""
        n = self.n_shards
        if keys_np.size == 0:
            return 0
        dest = shard_of_np(keys_np, n).astype(np.int64)
        per_shard = [np.flatnonzero(dest == i) for i in range(n)]
        max_len = max(idx.size for idx in per_shard)
        overflowed = 0
        for start in range(0, max_len, chunk):
            width = min(chunk, max_len - start)
            send = np.zeros((n, width, 4), np.uint32)
            meta = np.zeros((n, width), np.uint32)
            valid = np.zeros((n, width), bool)
            for i, idx in enumerate(per_shard):
                sl = idx[start : start + width]
                send[i, : sl.size] = keys_np[sl]
                meta[i, : sl.size] = meta_np[sl]
                valid[i, : sl.size] = True
            fn = self._bulk_insert_fn(width)
            self.rows, self.count, ovf = fn(
                self.rows, self.count, *self._place(send, meta, valid),
            )
            overflowed += int(jnp.sum(ovf))
        return overflowed

    def pack_programs(self):
        """``(index, chunk)`` of ``buckettable``'s packing under
        ``shard_map``: every shard packs its own row block on its own
        chip (fills, search index and chunks all stay split along axis
        0, shard i's part on chip i), and a chunk's first row is one
        replicated scalar, the same for every shard."""
        fns = self._step_cache.get("pack")
        if fns is None:
            A = P(self.axis)

            def index_fn(rows):
                # The index's depth follows the LOCAL row block, so its
                # specs come from tracing it on one.
                local = jax.ShapeDtypeStruct(
                    (rows.shape[0] // self.n_shards, rows.shape[1]),
                    rows.dtype)
                specs = jax.tree.map(
                    lambda _: A, jax.eval_shape(buckettable.pack_index, local))
                return shard_map(buckettable.pack_index, mesh=self.mesh,
                                 in_specs=A, out_specs=specs,
                                 check_vma=False)(rows)

            def chunk_fn(rows, index, start, chunk):
                return shard_map(
                    functools.partial(buckettable.pack_chunk, chunk=chunk),
                    mesh=self.mesh,
                    in_specs=(A, jax.tree.map(lambda _: A, index), P()),
                    out_specs=A, check_vma=False)(rows, index, start)

            fns = self._step_cache["pack"] = (
                jax.jit(index_fn),
                jax.jit(chunk_fn, static_argnames=("chunk",)))
        return fns

    def total_count(self) -> int:
        return int(jnp.sum(self.count))

    def contains_np(self, fps_np: np.ndarray) -> np.ndarray:
        """Batched membership probe against the sharded table.

        Mirrors the sharded insert addressing exactly: home shard from
        `_shard_of` (routed on the host), then the layout's local probe
        within that shard's row block, every shard on its own chip
        under ``shard_map`` (:func:`shard_contains`): no chip gathers
        from another's rows. Used by the host lane's cross-domain dedup
        guard; the caller holds the table lock."""
        if fps_np.size == 0:
            return np.zeros((0,), bool)
        keys, dest, pos = route_to_shards(
            np.asarray(fps_np, np.uint32).reshape(-1, 4), self.n_shards)
        return shard_contains(self.rows, keys, self.layout,
                              self.max_probes)[dest, pos]

    def drain_np(self) -> tuple[np.ndarray, np.ndarray]:
        if self.layout == "bucket":
            return buckettable.drain_np(
                buckettable.BucketTable(self.rows, self.count)
            )
        return hashtable.drain_np(
            hashtable.TableState(self.rows, self.count)
        )
