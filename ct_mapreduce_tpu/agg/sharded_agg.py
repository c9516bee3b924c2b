"""Exact-parity aggregator over the mesh-sharded dedup.

:class:`ShardedAggregator` is :class:`TpuAggregator` with the device
path swapped for :class:`~ct_mapreduce_tpu.agg.sharded.ShardedDedup`:
batches shard along the batch axis, keys route to their home table
shard over ICI ``all_to_all``, per-issuer counts come back ``psum``'d —
while the host-side exact lane, issuer registry, CRL/DN accumulation,
drain, and checkpoint contract stay identical. One process drives the
whole mesh (multi-host runs drive the global mesh via
``jax.distributed``; see ct_mapreduce_tpu.parallel.distributed).
"""

from __future__ import annotations

from datetime import datetime
from typing import Optional

import numpy as np

from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
from ct_mapreduce_tpu.agg.sharded import ShardedDedup, shard_of_np
from ct_mapreduce_tpu.core import packing
from ct_mapreduce_tpu.telemetry import trace
from ct_mapreduce_tpu.telemetry.metrics import incr_counter


def _pack_bits_np(flags: np.ndarray, nb: int) -> np.ndarray:
    """bool[B] → uint32[nb] bitmask (bit i of word w = lane w*32+i) —
    host mirror of ``pipeline._pack_bits``."""
    b = flags.shape[0]
    padded = np.pad(flags.astype(bool), (0, nb * 32 - b)).reshape(nb, 32)
    weights = (np.uint64(1) << np.arange(32, dtype=np.uint64))[None, :]
    return np.where(padded, weights, 0).sum(axis=1).astype(np.uint32)


def _unpack_bits_np(words: np.ndarray, n: int) -> np.ndarray:
    """uint32[..., nb] bitmask → bool[..., n] lanes."""
    bits = (words[..., :, None] >> np.arange(32, dtype=np.uint32)) & 1
    return bits.astype(bool).reshape(words.shape[:-1] + (-1,))[..., :n]


class _ShardedPreparsedOut:
    """Readback adapter: the sharded pre-parsed step's per-SHARD compact
    outputs, reassembled lazily into the per-CHUNK ``PreparsedStepOut``
    layout ``TpuAggregator._fold_preparsed`` consumes. Device arrays
    stay unmaterialized until ``.packed`` is first read (the fold), so
    the submit half remains fully asynchronous, exactly like the
    single-chip lane."""

    def __init__(self, packed_s, ovf_bits_s, counts, slot_of_orig,
                 c: int, k_chunks: int, chunk: int, flag_cap: int,
                 device_cap: int, num_issuers: int) -> None:
        self._packed_s = packed_s      # device int32[n_shards, 2+nbC+dcap]
        self._ovf_bits_s = ovf_bits_s  # device uint32[n_shards, nbC]
        self._counts = counts          # device int32[num_issuers]
        self._slot = slot_of_orig      # int64[n] original lane → shard slot
        self._c = c
        self._k = k_chunks
        self._b = chunk
        self._cap = flag_cap           # per-chunk cap of the fold layout
        self._dev_cap = device_cap     # per-shard cap of the device rows
        self._num_issuers = num_issuers
        self._built = None

    def _build(self):
        if self._built is not None:
            return self._built
        P = np.asarray(self._packed_s)
        counts = np.asarray(self._counts).astype(np.int32)
        n_shards = P.shape[0]
        c, cap, dcap = self._c, self._cap, self._dev_cap
        nb_c = -(-c // 32)
        wu_slots = _unpack_bits_np(
            P[:, 2:2 + nb_c].view(np.uint32), c).reshape(-1)
        ovf_slots = np.zeros((n_shards * c,), bool)
        spilled = False
        for s in range(n_shards):
            oc = int(P[s, 1])
            if oc == 0:
                continue
            if oc <= dcap:
                ids = P[s, 2 + nb_c:2 + nb_c + oc]
                ids = ids[ids < c]
                ovf_slots[s * c + ids] = True
            else:
                # Compacted-flag spill on this shard: decode its full
                # overflow bitmask (one extra fetch, all shards).
                if not spilled:
                    bits = np.asarray(self._ovf_bits_s).view(np.uint32)
                    spilled = True
                ovf_slots[s * c:(s + 1) * c] = _unpack_bits_np(
                    bits[s], c)
        # Back to original lane order, then into the [K, B]-chunked
        # packed rows the shared fold expects.
        wu = wu_slots[self._slot]
        ovf = ovf_slots[self._slot]
        k, b, nb = self._k, self._b, -(-self._b // 32)
        width = 2 + nb + cap + self._num_issuers
        packed = np.zeros((k, width), np.int32)
        over_bits = np.zeros((k, nb), np.uint32)
        for kk in range(k):
            w = wu[kk * b:(kk + 1) * b]
            o = ovf[kk * b:(kk + 1) * b]
            packed[kk, 0] = int(w.sum())
            oc = int(o.sum())
            packed[kk, 1] = oc
            packed[kk, 2:2 + nb] = _pack_bits_np(w, nb).view(np.int32)
            ids = np.full((cap,), b, np.int32)
            if 0 < oc <= cap:
                ids[:oc] = np.nonzero(o)[0][:cap]
            packed[kk, 2 + nb:2 + nb + cap] = ids
            over_bits[kk] = _pack_bits_np(o, nb)
        # psum'd per-issuer counts ride one chunk row (the fold sums
        # the count region across chunk rows).
        packed[0, 2 + nb + cap:] = counts[:self._num_issuers]
        self._built = (packed, over_bits)
        return self._built

    @property
    def packed(self) -> np.ndarray:
        return self._build()[0]

    @property
    def overflow_bits(self) -> np.ndarray:
        return self._build()[1]


class ShardedAggregator(TpuAggregator):
    def __init__(
        self,
        mesh,
        capacity: int = 1 << 22,
        batch_size: int = 4096,
        base_hour: int = packing.DEFAULT_BASE_HOUR,
        cn_prefixes: tuple[str, ...] = (),
        max_probes: int = 32,
        now: Optional[datetime] = None,
        dispatch_factor: float = 2.0,
        grow_at: float = 0.55,
        max_capacity: int = 1 << 28,
    ) -> None:
        self.mesh = mesh
        n = mesh.devices.size
        if batch_size % n:
            raise ValueError(f"batch_size {batch_size} must divide over {n} devices")
        # Auto-growth is a LOCKSTEP operation (every process must
        # rebuild + reinsert the same mesh-wide table at the same
        # point), but its trigger derives from per-process fill
        # estimates that diverge across hosts — a recipe for collective
        # deadlock. Until a replicated trigger exists, growth is
        # disabled when THIS mesh spans multiple processes (a
        # process-local mesh inside a multi-host job keeps growing
        # normally); probe overflow still spills to the exact host
        # lane, so counts stay exact.
        import jax

        mesh_procs = {d.process_index for d in mesh.devices.flat}
        if grow_at > 0 and len(mesh_procs) > 1:
            if jax.process_index() == min(mesh_procs):
                import sys

                print(
                    "ShardedAggregator: disabling table auto-growth — "
                    f"the mesh spans {len(mesh_procs)} processes; size "
                    "tableBits for the full run or re-shard via "
                    "checkpoint",
                    file=sys.stderr,
                )
            grow_at = 0.0
        from ct_mapreduce_tpu.agg.sharded import mesh_capacity

        self.dedup = ShardedDedup(
            mesh,
            capacity=mesh_capacity(n, capacity),
            base_hour=base_hour,
            max_probes=max_probes,
            dispatch_factor=dispatch_factor,
        )
        super().__init__(
            capacity=capacity,
            batch_size=batch_size,
            base_hour=base_hour,
            cn_prefixes=cn_prefixes,
            max_probes=max_probes,
            now=now,
            grow_at=grow_at,
            max_capacity=max_capacity,
        )
        # Load-factor arithmetic runs on the mesh-rounded slot count.
        self.capacity = self.dedup.capacity

    # -- hooks -----------------------------------------------------------
    def _layout_capacity_floor(self, cap: int) -> int:
        """Largest mesh-buildable capacity ≤ ``cap``: shards get
        power-of-two units, so halve the per-shard unit until the
        mesh-rounded total fits under the configured ceiling."""
        from ct_mapreduce_tpu.agg.sharded import mesh_capacity

        n = self.mesh.devices.size
        target = cap
        while target >= n:
            reach = mesh_capacity(n, target)
            if reach <= cap:
                return reach
            target //= 2
        return mesh_capacity(n, 1)

    def _make_table(self, capacity: int):
        return None  # state lives in self.dedup (sharded over the mesh)

    def _drain_table(self) -> tuple[np.ndarray, np.ndarray]:
        return self.dedup.drain_np()

    def _device_contains(self, fps: np.ndarray) -> np.ndarray:
        # Under the table lock, as on one chip: the mesh step donates
        # the rows, so a probe racing it would read a deleted array.
        with self._table_lock:
            return self.dedup.contains_np(fps)

    def _table_fill_exact(self) -> int:
        return self.dedup.total_count()

    def put_rows(self, data: np.ndarray):
        """One batch's rows from the host straight to their shards: row
        block i of n goes to chip i, which parses it (asynchronous, as
        the one-chip put). The only crossing of the host-device
        boundary a batch's rows make."""
        import jax

        with trace.span("shard.put", cat="device", bytes=int(data.nbytes),
                        shards=int(self.dedup.n_shards)):
            rows = jax.device_put(data, self.dedup.batch_sharding)
        incr_counter("shard", "row_bytes_h2d", value=float(data.nbytes))
        return rows

    def _device_step_preparsed(self, serials, serial_len, nah,
                               issuer_idx, insertable, flag_cap: int):
        """Pre-parsed lane over the mesh, host-routed.

        The walker path routes on device (dispatch + ``all_to_all``)
        because fingerprints only exist after the on-device parse. The
        pre-parsed lane's fingerprints are computable on the HOST from
        the sidecar's compact fields, so every lane's home shard is
        known before anything ships: lanes stable-sort by home shard,
        partition into per-shard ranges (padded to a shared power-of-
        two width C so compiled shapes stay log-bounded), and the
        device step is pure shard-local fingerprint+insert+counts —
        the ``all_to_all`` disappears and the ~59 B/lane wire win
        survives. Stable sort preserves lane order within a shard, so
        same-fingerprint duplicates resolve first-wins exactly like
        the single-chip lane (mesh=1 parity is exact, pinned by
        tests/test_sharded_preparsed.py)."""
        self._device_written = True
        k, b = np.asarray(serial_len).shape
        n = k * b
        ns = self.dedup.n_shards

        def flat(a, dtype):
            a = np.asarray(a, dtype)
            return np.ascontiguousarray(a.reshape((n,) + a.shape[2:]))

        ser = flat(serials, np.uint8)
        slen = flat(serial_len, np.int32)
        nh = flat(nah, np.int32)
        ii = flat(issuer_idx, np.int32)
        ins = flat(insertable, bool)

        fps = packing.fingerprints_np(ii, nh, ser, slen)
        dest = shard_of_np(fps, ns)
        perm = np.argsort(dest, kind="stable")
        per_shard = np.bincount(dest, minlength=ns)
        c = max(8, int(per_shard.max()))
        c = 1 << (c - 1).bit_length()  # pad to 2^k: bounded shape churn
        starts = np.zeros((ns + 1,), np.int64)
        starts[1:] = np.cumsum(per_shard)
        dsort = dest[perm].astype(np.int64)
        slot_sorted = dsort * c + (np.arange(n) - starts[dsort])
        slot_of_orig = np.empty((n,), np.int64)
        slot_of_orig[perm] = slot_sorted

        def route(a):
            out = np.zeros((ns * c,) + a.shape[1:], a.dtype)
            out[slot_sorted] = a[perm]
            return out

        cap = min(int(flag_cap), c)
        with trace.span("mesh.step_preparsed", cat="device",
                        shards=int(ns)), self._table_lock:
            packed_s, ovf_bits_s, counts = self.dedup.step_preparsed(
                route(ser), route(slen), route(nh), route(ii),
                route(ins), flag_cap=cap,
            )
        return _ShardedPreparsedOut(
            packed_s, ovf_bits_s, counts, slot_of_orig,
            c=c, k_chunks=k, chunk=b, flag_cap=int(flag_cap),
            device_cap=cap, num_issuers=packing.MAX_ISSUERS,
        )

    def _save_table_state(self):
        return self.dedup

    def _restore_table_state(self, saved) -> None:
        self.dedup = saved

    def _rebuild_table(self, new_capacity: int) -> int:
        self.dedup = ShardedDedup(
            self.mesh,
            capacity=self._mesh_capacity(new_capacity),
            base_hour=self.base_hour,
            max_probes=self.max_probes,
            dispatch_factor=self.dedup.dispatch_factor,
        )
        return self.dedup.capacity

    def _bulk_reinsert(self, keys: np.ndarray, meta: np.ndarray) -> int:
        return self.dedup.bulk_insert_np(keys, meta)

    def _device_step_packed(self, batch):
        self._device_written = True
        import jax

        # Rows the ingest path already placed (put_rows, ahead of the
        # dispatch lock) go through as they are; NumPy rows (a chunk
        # short of the batch, padded on the host; the per-entry lane)
        # are placed here, once. Either way the step sees the same
        # shapes and shardings: one program. Nothing reads rows back.
        data = batch.data
        if not isinstance(data, jax.Array):
            data = self.put_rows(data)
        incr_counter("shard", "row_bytes_d2h", value=0.0)
        with trace.span("mesh.step", cat="device",
                        shards=int(self.dedup.n_shards),
                        lanes=int(data.shape[0])), self._table_lock:
            return self.dedup.step(
                data,
                batch.length,
                batch.issuer_idx,
                batch.valid,
                now_hour=self._now_hour(),
                cn_prefixes=self._prefix_arr,
                cn_prefix_lens=self._prefix_lens,
            )

    def _topology_shards(self) -> int:
        return self.dedup.n_shards

    # -- checkpoint ------------------------------------------------------
    def _checkpoint_table(self):
        # The row-sharded arrays as they live on the mesh: the writer
        # packs each shard on its own chip and reads it off that chip,
        # and no array of the table's size is made on a single device.
        # The state type matches the dedup's layout so the codec writes
        # the right keys/meta + layout + n_shards fields. Only full
        # (ck01 / CTMRCK02 base) saves read the table: a delta
        # segment's rows come from the fold-time dirty log.
        from ct_mapreduce_tpu.ops import buckettable, hashtable

        state_cls = (buckettable.BucketTable
                     if self.dedup.layout == "bucket"
                     else hashtable.TableState)
        return state_cls(rows=self.dedup.rows, count=self.dedup.count)

    def _pack_programs(self):
        return self.dedup.pack_programs()

    def _restore_table(self, keys, meta, count, layout: str,
                       ckpt_shards: int, fill=None) -> None:
        # A packed base (``fill``) of this very topology — same layout,
        # shard count and capacity — rebuilds the rows it was packed
        # from and puts each shard's block on its chip. Anything else
        # restores by REINSERTION, not raw row copy: a checkpoint may
        # come from a different topology (single chip, another mesh
        # size) or layout, and a key's home shard, bucket, and probe
        # sequence all depend on both — only re-hashing every occupied
        # row is always correct.
        from ct_mapreduce_tpu.ops import buckettable

        self.table = None
        packed = fill is not None
        keys, meta, ckpt_cap = self._occupied_rows(keys, meta, fill)
        if (packed and layout == self.dedup.layout == "bucket"
                and ckpt_shards == self.dedup.n_shards
                and ckpt_cap == self.dedup.capacity):
            import jax

            self.dedup.rows = jax.device_put(
                buckettable.unpack_np(fill, keys, meta),
                self.dedup.batch_sharding)
            self.dedup.count = jax.device_put(
                np.asarray(count, np.int32), self.dedup.batch_sharding)
            return
        target_cap = max(self.dedup.capacity, ckpt_cap)
        self.dedup = ShardedDedup(
            self.mesh,
            capacity=self._mesh_capacity(target_cap),
            base_hour=self.base_hour,
            max_probes=self.max_probes,
            dispatch_factor=self.dedup.dispatch_factor,
        )
        overflow = self.dedup.bulk_insert_np(keys, meta)
        if overflow:
            raise RuntimeError(
                f"checkpoint restore overflowed {overflow} rows; "
                f"increase tableBits (capacity {self.dedup.capacity})"
            )
        self.capacity = self.dedup.capacity

    def _mesh_capacity(self, capacity: int) -> int:
        """Round capacity so each shard gets a power-of-two slice."""
        from ct_mapreduce_tpu.agg.sharded import mesh_capacity

        return mesh_capacity(self.mesh.devices.size, capacity)
