"""The on-device reduce state and its exact host fallback lane.

``TpuAggregator`` replaces the Redis-resident reduce state of the
reference (serial dedup sets, per-issuer CRL/DN sets,
/root/reference/storage/knowncertificates.go,
/root/reference/storage/issuermetadata.go) with:

- an HBM-resident dedup hash table (:mod:`ct_mapreduce_tpu.ops.hashtable`)
  driven by the fused ingest step (:mod:`ct_mapreduce_tpu.ops.pipeline`),
- a host-side issuer registry mapping SHA-256(SPKI) identities to the
  dense indices the device ops use,
- host-side CRL/DN string sets (tiny, string-typed — SURVEY.md §7
  layer 3 keeps them off-device), fed by device-extracted byte windows
  so the host never re-parses a certificate it has seen the shape of,
- an **exact host lane** for every lane the device flags
  (parse failure / oversized serial / meta range / probe overflow),
  preserving the reference's per-entry tolerance contract
  (/root/reference/cmd/ct-fetch/ct-fetch.go:206-225).

Determinism note: a certificate either always takes the device path or
always takes the host path for the routing predicates that are
functions of the cert alone. Probe overflow is the exception — an
overflowed key spills to the host lane, and after a grow-and-rehash
(load-factor policy) the same key may later insert on device — so the
two dedup domains can OVERLAP. Exactness rests on the cross-domain
guards: the host lane probes device membership before counting
(`_device_known_flags`), the device lane checks the host sets on
unknown lanes (cross-encoding guard in `_consume_out`), and `drain()`
subtracts the host∩device overlap in one batched probe.

``drain()`` reconstructs exactly what ``storage-statistics`` prints
(/root/reference/cmd/storage-statistics/storage-statistics.go:28-99):
per-(issuer, expDate) serial counts from the table's meta words plus
the host sets, and per-issuer CRL/DN sets.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from datetime import datetime, timezone
from typing import Optional
from urllib.parse import urlparse

import numpy as np

from ct_mapreduce_tpu import native
from ct_mapreduce_tpu.agg import ckpt
from ct_mapreduce_tpu.core import der as hostder
from ct_mapreduce_tpu.core import packing
from ct_mapreduce_tpu.core.types import ExpDate, Issuer
from ct_mapreduce_tpu.filter.cache import content_token, serial_hash
from ct_mapreduce_tpu.filter.spill import SpillCaptureRing
from ct_mapreduce_tpu.ops import buckettable, der_kernel, hashtable, pipeline
from ct_mapreduce_tpu.telemetry import trace
from ct_mapreduce_tpu.telemetry.metrics import (
    incr_counter,
    measure,
    set_gauge,
)


def _rep_windows_numpy(rows2d, row_sel, issuers, o, ln):
    """Representative lane per distinct (issuer, window bytes), by
    gathering every lane's window and sorting them: tens of MB of
    temporaries a 65,536-lane batch. The routine for what the native
    pass does not read, and the oracle of its tests."""
    width = int(ln.max(initial=0))
    k = row_sel.shape[0]
    cols = o[:, None] + np.arange(width, dtype=o.dtype)[None, :]
    cols = np.clip(cols, 0, rows2d.shape[1] - 1)
    wins = rows2d[row_sel[:, None], cols]
    wins[np.arange(width)[None, :] >= ln[:, None]] = 0
    # Row-wise unique via a contiguous byte-row void view —
    # ~an order of magnitude cheaper than np.unique(axis=0)'s
    # int64 lexsort at these shapes (measured on the e2e leg).
    tag8 = np.empty((k, width + 6), np.uint8)
    tag8[:, 0:4] = (
        issuers.astype(np.uint32).view(np.uint8).reshape(k, 4))
    tag8[:, 4:6] = ln.astype(np.uint16).view(np.uint8).reshape(k, 2)
    tag8[:, 6:] = wins
    v = np.ascontiguousarray(tag8).view(
        np.dtype((np.void, tag8.shape[1])))
    _, first = np.unique(v.ravel(), return_index=True)
    return first


def _window_reps(rows2d, row_sel, issuers, o, ln):
    """One representative lane per distinct (issuer, window bytes) of
    the selection, and the lanes that took the NumPy routine.

    The native pass (``ctmr_unique_windows``) reads each window where it
    lies and copies none. What it does not read takes
    :func:`_rep_windows_numpy` as every lane did before: all lanes
    where the library did not load or the rows are not contiguous
    bytes, else the lanes it hands back (a negative length, a window
    that does not lie wholly inside its row), whose clipping is that
    routine's. What decides is in the input; there is no setting."""
    nothing = np.zeros((0,), np.int64)
    if int(ln.max(initial=0)) <= 0:
        # No lane has a byte: no representative, not one empty window
        # an issuer (a batch of certificates without the extension).
        return nothing, nothing
    got = native.unique_windows(rows2d, row_sel, issuers, o, ln)
    if got is None:
        every = np.arange(row_sel.shape[0], dtype=np.int64)
        return _rep_windows_numpy(rows2d, row_sel, issuers, o, ln), every
    first, rest = got
    if rest.size:
        sub = _rep_windows_numpy(
            rows2d, row_sel[rest], issuers[rest], o[rest], ln[rest])
        first = np.concatenate([first, rest[sub]])
    return first, rest


def _donating_backend() -> bool:
    """Whether the walker step donates its row buffer: everywhere but
    on the CPU backend, whose XLA cannot alias this layout and warns on
    every dispatch."""
    import jax

    return jax.default_backend() != "cpu"


# Layout selection lives beside the insert dispatch (CTMR_TABLE,
# default bucket); re-exported here for the aggregator's callers.
_table_layout = pipeline.table_layout

#: Share of ``grow_at`` past which a round's end makes the doubled
#: table's programs ready (`TpuAggregator.prepare_growth`): load 0.656
#: at the default ``tableGrowAt`` 0.7. A table below it is more than a
#: sixteenth of its threshold away from growing and pays nothing.
GROW_PREPARE_AT = 15 / 16


class IssuerRegistry:
    """Dense issuer indexing for device ops.

    Maps issuer certificates (by raw DER, cached) to small integer
    indices; index → :class:`Issuer` (base64url(SHA-256(SPKI)),
    /root/reference/storage/types.go:104-141) for drains and reports.
    """

    def __init__(self) -> None:
        self._by_der: dict[bytes, int] = {}
        self._by_issuer_id: dict[str, int] = {}
        self._issuers: list[Issuer] = []
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._issuers)

    def get_or_assign(self, issuer_der: bytes) -> int:
        with self._lock:
            idx = self._by_der.get(issuer_der)
            if idx is not None:
                return idx
            fields = hostder.parse_cert(issuer_der)
            issuer = Issuer.from_spki(fields.spki)
            iid = issuer.id()
            idx = self._by_issuer_id.get(iid)
            if idx is None:
                # Indices are unbounded: only the DEVICE meta word packs
                # the issuer in META_ISSUER_BITS, and the pipeline's
                # idx_ok gate (ops/pipeline.py) already routes lanes
                # with idx >= MAX_ISSUERS to the exact host lane, which
                # keys by plain ints — so a full-log replay that blows
                # past 16,384 issuers degrades to host-exact counting
                # for the excess issuers instead of crashing ingest.
                idx = len(self._issuers)
                self._issuers.append(issuer)
                self._by_issuer_id[iid] = idx
            self._by_der[issuer_der] = idx
            return idx

    def assign_issuer(self, issuer: Issuer) -> int:
        """Index for an already-constructed :class:`Issuer` identity
        (no DER in hand — e.g. folding another worker's checkpointed
        registry into a merged view)."""
        with self._lock:
            iid = issuer.id()
            idx = self._by_issuer_id.get(iid)
            if idx is None:
                idx = len(self._issuers)
                self._issuers.append(issuer)
                self._by_issuer_id[iid] = idx
            return idx

    def index_of_issuer_id(self, issuer_id: str) -> Optional[int]:
        return self._by_issuer_id.get(issuer_id)

    def ids_from(self, start: int) -> list[str]:
        """Issuer-id strings for indices >= ``start``, in index order —
        the registry's append-only suffix since a shadow length was
        taken (CTMRCK02 segment diffs)."""
        with self._lock:
            return [iss.id() for iss in self._issuers[start:]]

    def issuer_at(self, idx: int) -> Issuer:
        return self._issuers[idx]

    def to_json(self) -> str:
        return json.dumps([iss.id() for iss in self._issuers])

    @classmethod
    def from_json(cls, raw: str) -> "IssuerRegistry":
        reg = cls()
        for iid in json.loads(raw):
            idx = len(reg._issuers)
            reg._issuers.append(Issuer.from_string(iid))
            reg._by_issuer_id[iid] = idx
        return reg


@dataclass
class IngestResult:
    """Per-batch outcome, aligned with the input entry order."""

    was_unknown: np.ndarray  # bool[n]
    filtered: np.ndarray  # bool[n] — CA / expired / CN filter
    exp_hours: np.ndarray  # int32[n] (0 where filtered/unparseable)
    serials: list[Optional[bytes]]  # raw serial bytes per entry
    issuer_idx: np.ndarray  # int32[n]
    host_lane_count: int = 0


class PendingIngest:
    """The async half of :meth:`TpuAggregator.ingest_packed`.

    Device work for every chunk has been DISPATCHED (JAX dispatch is
    asynchronous; the steps chain in submission order on the donated
    table state), but no result has been read back. ``complete()``
    performs the D2H reads and the exact host-lane work and returns the
    :class:`IngestResult`.

    This is the TPU analog of the reference's download→store pipeline
    overlap (goroutines + a 16,384-slot channel,
    /root/reference/cmd/ct-fetch/ct-fetch.go:132,398-488): while the
    device chews on batch N, the host decodes and packs batch N+1
    instead of blocking on N's readback.
    """

    batch = 0  # the raw chunk's number in the trace; the sink sets it

    def __init__(self, agg: "TpuAggregator", chunks, res: IngestResult,
                 data: np.ndarray, length: np.ndarray) -> None:
        self._agg = agg
        self._chunks = chunks  # [(batch, device_pos, lane_of, out)]
        self._res = res
        self._data = data
        self._length = length
        self._done = False
        # The store thread completes pendings in submission order
        # while a report's drain or a checkpoint may call
        # complete_outstanding from another thread; the per-pending
        # lock makes the race a cheap no-op for whoever loses it.
        self._lock = threading.Lock()

    def complete(self) -> IngestResult:
        with self._lock:
            if self._done:
                return self._res
            # Claimed BEFORE the fold: a fold that raises must not be
            # retried by a later completer — a partial fold re-applied
            # would double-count.
            self._done = True
            agg = self._agg
            # All host-state fold-ins serialize on the aggregator-wide
            # fold lock (metrics, issuer_totals, host_serials, and the
            # cross-encoding guard are shared mutable state). FIFO order
            # is preserved because every completer — the sink's drain
            # and complete_outstanding alike — takes the OLDEST pending
            # first and blocks on its per-pending lock.
            with trace.span("device.fold", cat="device") as sp, \
                    agg._fold_lock:
                with contextlib.suppress(ValueError):
                    agg._outstanding.remove(self)
                agg._inflight_lanes = max(
                    0, agg._inflight_lanes - len(self._res.was_unknown))
                res = self._res
                host_lane_total = 0
                for batch, device_pos, lane_of, out in self._chunks:
                    host_pos = agg._consume_out(batch, out, device_pos, res,
                                                lane_of, host_rows=self._data)
                    host_lane_total += agg._host_lanes(
                        host_pos,
                        lambda pos: self._data[
                            pos, : self._length[pos]].tobytes(),
                        res,
                    )
                agg.metrics["host_lane"] += host_lane_total
                res.host_lane_count = host_lane_total
                agg._filter_fold_done(sp)
                incr_counter("aggregator", "batches")
            return self._res


@dataclass
class _PreparsedPlan:
    """Host-evaluated routing for one pre-parsed submit: every filter
    and device-exactness predicate of ``pipeline.local_lanes``,
    computed from the sidecar with mirrored arithmetic. The device sees
    only ``insertable``; everything else folds host-side at complete()
    time without any per-lane D2H."""

    sidecar: object  # leafpack.Sidecar
    issuer_idx: np.ndarray  # int32[n]
    valid: np.ndarray  # bool[n]
    f_ca: np.ndarray  # bool[n]
    f_expired: np.ndarray
    f_cn: np.ndarray
    cn_passed: np.ndarray  # the CN predicate's yes and its "the host
    cn_undec: np.ndarray  # lane decides"; all False without a filter
    passed: np.ndarray
    insertable: np.ndarray
    static_host_lane: np.ndarray  # host-lane lanes known before insert
    serial_bytes: np.ndarray  # uint8[n, MAX_SERIAL_BYTES]
    host_rows: np.ndarray  # uint8[n, pad]
    length: np.ndarray  # int32[n]
    n: int
    chunk: int  # device chunk width (batch_size)
    flag_cap: int


@dataclass
class _FilterFold:
    """What the CN filter decided in the fold under way (its holder has
    the fold lock): lanes by the device's or the pre-parsed lane's
    verdict, the positions handed to the exact host lane because
    neither could decide, and what that lane dropped of them.
    ``TpuAggregator._filter_fold_done`` emits and clears it."""

    passed: int = 0
    dropped: int = 0
    undecided: set = field(default_factory=set)  # positions in the result
    host_dropped: int = 0
    filtered_cn: int = 0  # every lane the fold dropped by its CN


#: Rows of the device's prefix array under a CN filter, whatever the
#: directive holds: the source's one prefix and a CA's family of names
#: fit, and a longer list takes the next power of two (one more program).
CN_PREFIX_ROWS = 8


class PendingPreparsed:
    """Async half of :meth:`TpuAggregator.ingest_preparsed_submit` —
    the pre-parsed lane's :class:`PendingIngest`: same FIFO /
    claim-before-fold / fold-lock contract, but the readback is the
    step's single packed array (plus the overflow-bitmask fallback on
    a compacted-flag spill) instead of twelve per-lane buffers."""

    batch = 0  # as PendingIngest's

    def __init__(self, agg: "TpuAggregator", out, plan: _PreparsedPlan,
                 res: IngestResult) -> None:
        self._agg = agg
        self._out = out  # pipeline.PreparsedStepOut
        self._plan = plan
        self._res = res
        self._done = False
        self._lock = threading.Lock()

    def complete(self) -> IngestResult:
        with self._lock:
            if self._done:
                return self._res
            self._done = True
            agg = self._agg
            with trace.span("device.fold", cat="device") as sp, \
                    agg._fold_lock:
                with contextlib.suppress(ValueError):
                    agg._outstanding.remove(self)
                agg._inflight_lanes = max(
                    0, agg._inflight_lanes - len(self._res.was_unknown))
                agg._fold_preparsed(self._out, self._plan, self._res)
                agg._filter_fold_done(sp)
                incr_counter("aggregator", "batches")
            return self._res


@dataclass
class AggregateSnapshot:
    """Drained reduce state — the material of storage-statistics."""

    counts: dict[tuple[str, str], int]  # (issuerID, expDateID) → serials
    crls: dict[str, set[str]]  # issuerID → CRL DP URLs
    dns: dict[str, set[str]]  # issuerID → issuer DN strings
    total: int = 0
    # Signature-verification outcomes (round 13): per-issuer embedded-
    # SCT verdict counts. Empty when verifySignatures is off — every
    # pre-round-13 consumer sees byte-identical reports.
    verified: dict[str, int] = field(default_factory=dict)
    failed: dict[str, int] = field(default_factory=dict)

    def issuers(self) -> list[str]:
        out = {iss for iss, _ in self.counts}
        out.update(self.crls)
        out.update(self.dns)
        return sorted(out)


_pack_out_cache: dict = {}


def _pack_out(out):
    """Pack a step's small per-lane outputs into ONE int32[7, B] device
    array (bools as bit flags, the six int fields as rows).

    Every separate device-buffer read is its own D2H round trip, so
    the consume path fetches one packed array instead of twelve
    buffers. Cached per output type (StepOut/ShardedStepOut
    carry different flag sets) and per filter on or off (under a CN
    filter the flags word also says what the predicate said of each
    lane; without one the program is the one it was); jit itself caches
    per shape."""
    import jax
    import jax.numpy as jnp

    cn_said = out.cn_undecidable is not None
    key = (type(out), cn_said)
    fn = _pack_out_cache.get(key)
    if fn is None:
        has_dropped = hasattr(out, "dispatch_dropped")

        @jax.jit
        def fn(o):
            flags = (
                o.host_lane.astype(jnp.int32)
                | (o.was_unknown.astype(jnp.int32) << 1)
                | (o.filtered_ca.astype(jnp.int32) << 2)
                | (o.filtered_expired.astype(jnp.int32) << 3)
                | (o.filtered_cn.astype(jnp.int32) << 4)
                | (o.probe_overflow.astype(jnp.int32) << 5)
                | ((o.dispatch_dropped.astype(jnp.int32) << 6)
                   if has_dropped else 0)
            )
            if cn_said:
                flags = (flags
                         | (o.cn_undecidable.astype(jnp.int32) << 7)
                         | (o.cn_passed.astype(jnp.int32) << 8))
            return jnp.stack(
                [flags, o.not_after_hour, o.serial_len,
                 o.crldp_off, o.crldp_len,
                 o.issuer_name_off, o.issuer_name_len], axis=0)

        _pack_out_cache[key] = fn
    return fn(out)


def _reinsert_chunks(table, keys, meta, valid, max_probes: int):
    """All reinsert chunks in ONE jitted execution; overflow count
    accumulates on device and is read back once by the caller."""
    import functools as _functools

    import jax
    import jax.numpy as jnp

    @_functools.partial(jax.jit, static_argnames=("max_probes",),
                        donate_argnums=(0,))
    def run(table, keys, meta, valid, max_probes):
        def body(i, carry):
            table, ovf = carry
            table, _wu, o = pipeline.table_insert(
                table, keys[i], meta[i], valid[i], max_probes=max_probes
            )
            return table, ovf + o.sum(dtype=jnp.int32)

        return jax.lax.fori_loop(
            0, keys.shape[0], body, (table, jnp.int32(0))
        )

    return run(table, keys, meta, valid, max_probes=max_probes)


class TpuAggregator:
    def __init__(
        self,
        capacity: int = 1 << 22,
        batch_size: int = 4096,
        base_hour: int = packing.DEFAULT_BASE_HOUR,
        cn_prefixes: tuple[str, ...] = (),
        max_probes: int = 32,
        now: Optional[datetime] = None,
        grow_at: float = 0.55,
        max_capacity: int = 1 << 28,
    ) -> None:
        # Round-5 grow-livelock fix: round the ceiling DOWN to a
        # capacity the active layout can actually build — bucket
        # layouts only reach 24·2^k slots, open layouts powers of two;
        # neither ever reaches a ragged 2^m+r ceiling — so maybe_grow's
        # at-ceiling guard can fire. Without this, a table at the
        # clamped bucket capacity saw capacity < max_capacity forever
        # and re-ran a full drain+rebuild+reinsert on every batch past
        # the threshold — gaining zero slots each time. Set before the
        # table exists: _make_table clamps its round-up to this ceiling
        # (rows are 512 B/bucket; a silent 2x overshoot would double
        # HBM use).
        self.max_capacity = self._layout_capacity_floor(max_capacity)
        # Serializes host-state fold-ins (PendingIngest.complete /
        # _consume_out / _host_lanes) across threads — a report's
        # drain completes from a thread other than the store's.
        self._fold_lock = threading.Lock()
        # Guards self.table swaps vs concurrent reads: the donated step
        # invalidates the previous table buffer, so a contains probe or
        # checkpoint read racing a submit would touch a deleted array.
        # What a holder may count on: self.table is a live, fully-
        # stepped buffer, and device work DISPATCHED against it under
        # the lock (a probe, the query plane's snapshot_copy) reads it
        # before any later step's donation reuses its memory — every
        # step swaps self.table under this lock, so it is dispatched
        # after; one device runs its programs in dispatch order; and a
        # donation waits for the reads enqueued before it. So a
        # device-side reader may release the lock once its program is
        # dispatched and wait for the result outside; only a host
        # fetch (np.asarray) has to finish under it.
        # Lock order where both are held: _fold_lock, then _table_lock.
        self._table_lock = threading.RLock()
        # Serializes whole checkpoint writes: the fleet cadence thread
        # (ingest/fleet.py epoch ticks) and the run's own save path can
        # both reach save_checkpoint; interleaved writers are each
        # atomic (temp + rename) but doing the drain + serialize work
        # twice concurrently is waste and widens buffer-lifetime
        # exposure for no benefit.
        self._save_lock = threading.Lock()
        # What the save in progress wrote: (kind, bytes in, bytes out);
        # under the save lock.
        self._save_note = ("noop", 0, 0)
        self.table = self._make_table(capacity)
        # Bucket tables round capacity up to whole buckets; load-factor
        # arithmetic must use the real slot count.
        self.capacity = getattr(self.table, "capacity", capacity)
        self.batch_size = batch_size
        self.base_hour = base_hour
        self.max_probes = max_probes
        # Load-factor policy: when the (estimated) fill would exceed
        # grow_at × capacity, the table grows-and-rehashes to the next
        # power of two (up to max_capacity; past the cap, probe
        # overflow spills lanes to the exact host lane with the
        # `overflow` metric — counts stay exact either way). grow_at
        # <= 0 disables growth. The default 0.55 sits just below the
        # measured knee of the bucket table's load curve (one v5e,
        # docs/load_sweep_r04_bucket.log: 3.58M entries/s at 25% load,
        # 2.20M at 50%, 0.63M at 75% — past ~55% the Poisson tail of
        # full 24-slot buckets forces hop rounds), so steady state
        # operates in the 27-55% band at 2.2-3.6M/s.
        self.grow_at = grow_at
        # Host-side running fill estimate: device inserts folded in at
        # complete() time, plus lanes currently in flight (upper
        # bound). Exact fill is read from the device only when the
        # estimate trips the threshold.
        self._table_fill = 0
        self._inflight_lanes = 0
        # Growth on the device (`grow`, `prepare_growth`): the capacity
        # whose programs were run once against a scratch table and so
        # sit compiled in jit's caches, and what those programs are
        # shaped by beside the table: the last step's batch arrays and
        # the widths membership probes came in.
        self._grow_ready_for = 0
        self._step_shapes: Optional[tuple] = None
        self._contains_shapes: set[tuple] = set()
        self.registry = IssuerRegistry()
        self._fixed_now = now
        # Host-exact lane state: (issuer_idx, exp_hour) → set of serial bytes.
        self.host_serials: dict[tuple[int, int], set[bytes]] = {}
        # Per-issuer metadata (strings stay host-side).
        self.crl_sets: dict[int, set[str]] = {}
        self.dn_sets: dict[int, set[str]] = {}
        self._crl_raw_seen: set[tuple[int, bytes]] = set()
        self._dn_raw_seen: set[tuple[int, bytes]] = set()
        # Device-side per-issuer unknown totals (running).
        self.issuer_totals = np.zeros((packing.MAX_ISSUERS,), np.int64)
        # Per-issuer embedded-SCT verdict counts (round 13), fed by the
        # verify lane (verify/lane.py) under the fold lock; all-zero
        # (and absent from reports) unless verifySignatures is on.
        self.verify_verified = np.zeros((packing.MAX_ISSUERS,), np.int64)
        self.verify_failed = np.zeros((packing.MAX_ISSUERS,), np.int64)
        # Submitted-but-not-completed pipelined ingests (FIFO).
        self._outstanding: list[PendingIngest] = []
        # False until the first device-step submit: lets the host lane
        # skip cross-domain membership probes entirely for host-only
        # usage (each probe is a device dispatch + synchronous read).
        self._device_written = False
        # Set False by a sink that never materializes PEMs: skips the
        # per-entry serial-bytes construction in `_consume_out`.
        self.want_serials = True
        # Filter capture (round 15): when enabled, every first-seen
        # serial's BYTES are retained per (issuer_idx, exp_hour) so the
        # reduce state can compile crlite-style filter artifacts — the
        # device table keeps only hashed fingerprints, which cannot
        # seed a cross-run-deterministic filter. None = off (default):
        # zero overhead and byte-identical checkpoints.
        self.filter_capture: Optional[dict[tuple[int, int],
                                           set[bytes]]] = None
        # Exact per-group XOR content hashes for the dict capture
        # (CTMRFL02 dirty tracking): maintained incrementally alongside
        # first-seen capture; None when capture is off, the ring owns
        # its own hashes, or exactness was lost (restored snapshot
        # without stored hashes). A missing/None value only costs a
        # token recomputation — never a wrong reuse.
        self.filter_capture_hashes: Optional[dict[tuple[int, int],
                                                  int]] = None
        # Checkpoint-time filter emission (configure_filter_emission):
        # empty path = no artifact written.
        self.emit_filter_path = ""
        self.filter_fp_rate = 0.01
        self.filter_fmt = ""  # "" = resolve_format default
        # Checkpoint-time incremental build cache (CTMRFL02): clean
        # groups' cascades carry over between emissions.
        self._filter_build_cache = None
        self.set_cn_prefixes(cn_prefixes)
        self._cn_fold = _FilterFold()
        self.metrics: dict[str, int] = {
            "inserted": 0, "known": 0, "filtered_ca": 0, "filtered_expired": 0,
            "filtered_cn": 0, "host_lane": 0, "parse_errors": 0, "overflow": 0,
            "dispatch_spill": 0,
        }
        # Serializes checkpoint-time filter emission, which runs OUTSIDE
        # _save_lock (the checkpoint bytes land atomically before the
        # build starts; a multi-second scaled build must not block the
        # fleet-cadence save fan-out). GroupBuildCache is not
        # thread-safe, so overlapping emissions still serialize here.
        self._emit_lock = threading.Lock()
        # Incremental checkpoints (CTMRCK02, agg/ckpt.py): the per-tick
        # dirty log the fold paths append to under _fold_lock, armed
        # only after a save/load established a durable base at
        # _ckpt_path (non-checkpointing runs record nothing). The save
        # path turns the log into one delta segment; any event that
        # breaks O(churn) replayability (grow/rehash, serial-less
        # folds, a recorded/inserted count mismatch, segment budget)
        # poisons the log and forces the next save to anchor (fresh
        # full base).
        self._ckpt_knobs = None
        self._ckpt_track = False
        self._ckpt_dirty_lost = False
        self._ckpt_rows: list[tuple[int, int, bytes]] = []
        self._ckpt_host_adds: list[tuple[int, int, bytes]] = []
        self._ckpt_row_bytes = 0
        self._ckpt_dev_inserted = 0
        self._ckpt_path = ""
        self._ckpt_base_sha = ""
        self._ckpt_tip_token = ""
        self._ckpt_chain_len = 0
        # Snapshot-diff shadows from the last durable tick for the
        # small O(issuers) structures (registry length, totals/verify
        # vectors, CRL/DN sets).
        self._ckpt_shadow: Optional[dict] = None

    # -- state hooks (overridden by the mesh-sharded subclass) -----------
    def _layout_capacity_floor(self, cap: int) -> int:
        """Largest capacity ≤ ``cap`` the active layout can build.

        Bucket tables hold 24·2^k slots; open-addressed tables any
        power of two (growth doubles from either, so a floored ceiling
        stays exactly reachable). The growth ceiling is rounded THROUGH
        this at construction so ``capacity >= max_capacity`` is
        reachable and the at-ceiling guard can fire."""
        if _table_layout() == "bucket":
            return buckettable.bucket_count(cap, cap) * buckettable.SLOTS
        if cap & (cap - 1):
            cap = 1 << (cap.bit_length() - 1)
        return cap

    def _make_table(self, capacity: int):
        if _table_layout() == "bucket":
            return buckettable.make_table(
                capacity, max_capacity=self.max_capacity)
        return hashtable.make_table(capacity)

    def _topology_shards(self) -> int:
        """How many key-addressed shards this aggregator's table uses.

        Checkpoint slot positions are only meaningful under the
        topology that wrote them (a mesh-sharded writer addresses
        dest * nb_local + local hash); the value is recorded in every
        snapshot so a reader with a different topology re-hashes
        instead of trusting positions."""
        return 1

    def put_rows(self, data: np.ndarray):
        """Start the H2D transfer of one batch's rows and say where
        they live: here the default device (asynchronous: the caller
        enqueues it ahead of the dispatch lock, so the transfer rides
        beside the previous step). A mesh puts each row block straight
        on the chip that will parse it."""
        import jax

        return jax.device_put(data)

    def _checkpoint_table(self):
        """The table state a full save copies off the device: ``rows``
        and ``count`` as they live there (one chip's arrays here; a
        mesh's row-sharded arrays, which the copy reads shard by shard,
        each off its own chip). Caller holds the table lock."""
        return self.table

    def _drain_table(self) -> tuple[np.ndarray, np.ndarray]:
        if isinstance(self.table, buckettable.BucketTable):
            return buckettable.drain_np(self.table)
        return hashtable.drain_np(self.table)

    def _device_contains(self, fps: np.ndarray) -> np.ndarray:
        """bool[n]: are these fingerprints present in the device table?

        Dispatch AND materialization run under the table lock: the
        donated step invalidates the previous table buffer, so a probe
        racing a concurrent submit could read a deleted array.

        Probe batches are padded to the next power of two (min 16) so
        the jitted contains kernel compiles once per log bucket, not
        once per ragged host-lane count — the same log-bounded
        compile-shape rule the sharded dispatch uses (padding lanes'
        results are sliced off; a spurious hit on a zero key costs
        nothing because the lane is discarded)."""
        import jax.numpy as jnp

        n = int(fps.shape[0])
        if n == 0:
            return np.zeros((0,), bool)
        width = max(16, 1 << (n - 1).bit_length())
        if width != n:
            fps = np.pad(np.asarray(fps), ((0, width - n), (0, 0)))
        self._contains_shapes.add((fps.shape, np.dtype(fps.dtype).str))
        with self._table_lock:
            if isinstance(self.table, buckettable.BucketTable):
                out = np.asarray(
                    buckettable.contains(self.table, jnp.asarray(fps),
                                         max_probes=self.max_probes),
                )
            else:
                out = np.asarray(
                    hashtable.contains(self.table, jnp.asarray(fps),
                                       max_probes=self.max_probes),
                )
        return out[:n]

    # -- load-factor policy ---------------------------------------------
    def _table_fill_exact(self) -> int:
        """Occupied-slot count, synced from the device."""
        with self._table_lock:
            return int(np.asarray(self.table.count))

    def _rebuild_table(self, new_capacity: int) -> int:
        """Fresh empty table at ``new_capacity``; returns the actual
        capacity (bucket layouts round up to whole buckets,
        mesh-sharded subclasses round to the mesh)."""
        self.table = self._make_table(new_capacity)
        return getattr(self.table, "capacity", new_capacity)

    def _bulk_reinsert(self, keys: np.ndarray, meta: np.ndarray) -> int:
        """Re-hash drained rows into the (fresh) table; returns the
        number of rows that overflowed their probe chains.

        One device EXECUTION for the whole reinsert (fori_loop over
        chunk-shaped inserts) with one readback at the end, instead
        of a dispatch and a D2H read per chunk."""
        import jax.numpy as jnp

        n = len(keys)
        if n == 0:
            return 0
        chunk = min(1 << 16, max(1, n))
        n_chunks = -(-n // chunk)
        pad = n_chunks * chunk - n
        k = np.pad(keys, ((0, pad), (0, 0))).reshape(n_chunks, chunk, 4)
        m = np.pad(meta, (0, pad)).reshape(n_chunks, chunk)
        v = np.pad(np.ones((n,), bool), (0, pad)).reshape(n_chunks, chunk)
        self.table, ovf = _reinsert_chunks(
            self.table, jnp.asarray(k), jnp.asarray(m), jnp.asarray(v),
            max_probes=self.max_probes,
        )
        return int(np.asarray(ovf))

    def _grow_target(self, need: int) -> int:
        target = self.capacity
        while need > self.grow_at * target:
            target *= 2
        return min(target, self.max_capacity)

    def maybe_grow(self, incoming: int = 0) -> None:
        """Grow-and-rehash when the upper-bound fill estimate (folded
        inserts + in-flight lanes + the batch about to be submitted)
        crosses ``grow_at`` × capacity. Cheap host arithmetic on the
        common path; the exact device count is read only when the
        estimate trips."""
        if self.grow_at <= 0 or self.capacity >= self.max_capacity:
            return
        upper = self._table_fill + self._inflight_lanes + incoming
        if upper <= self.grow_at * self.capacity:
            return
        self.complete_outstanding()  # grow must not strand dispatches
        exact = self._table_fill_exact()
        self._table_fill = exact
        target = self._grow_target(exact + incoming)
        if target > self.capacity:
            self.grow(target)

    def _save_table_state(self):
        return self.table

    def _restore_table_state(self, saved) -> None:
        self.table = saved

    def _note_load(self) -> None:
        """The table's two gauges, set together wherever the fill or
        the capacity moved: the load the growth policy follows and the
        slots it is a share of."""
        set_gauge("aggregator", "table_load",
                  value=self._table_fill / self.capacity)
        set_gauge("aggregator", "table_slots", value=float(self.capacity))

    def _splits_on_device(self) -> bool:
        """Whether this table doubles where it lives
        (`buckettable.grow_rehash`): one chip's bucket table. A mesh
        (its state is `self.dedup`), the open layout and a host-resident
        snapshot grow through the host, as every table did."""
        return (isinstance(self.table, buckettable.BucketTable)
                and not isinstance(self.table.rows, np.ndarray))

    def _split_table(self) -> Optional[tuple[int, int]]:
        """One doubling on the device, under the table lock: ``(rows,
        rehomed)``, or None where a row that lay past a full bucket
        found no room in the doubled table either (the old table is
        then still the live one). The old table is freed as its name is
        rebound; no row of either crosses to the host."""
        import jax

        with trace.span("grow.rehash", cat="device") as sp:
            new, rehomed, overflowed = buckettable.grow_rehash(
                self.table, max_probes=self.max_probes)
            # The one readback (three scalars) is also the wait for
            # the program.
            rows, rehomed, overflowed = (
                int(x) for x in jax.device_get(
                    (new.count, rehomed, overflowed)))
            sp.set(rows=rows, rehomed=rehomed,
                   split=("mosaic" if buckettable.split_runs_compiled()
                          else "interpret"))
        if overflowed:
            return None
        self.table = new
        self.capacity = new.capacity
        return rows, rehomed

    def _rehash_through_host(self, new_capacity: int) -> int:
        """The growth every layout can do: drain the occupied rows to
        the host, build a fresh table and insert them again. A reinsert
        that probe-overflows (pathological / adversarial key cluster)
        retries at double capacity up to the ceiling; if it still
        overflows, the ORIGINAL state is restored and the error raised.
        Returns the rows re-hashed. Caller holds the table lock."""
        keys, meta = self._drain_table()
        saved = self._save_table_state()
        cap = new_capacity
        while True:
            actual = self._rebuild_table(cap)
            overflow = self._bulk_reinsert(keys, meta)
            if not overflow:
                break
            if cap >= self.max_capacity:
                self._restore_table_state(saved)
                raise RuntimeError(
                    f"table grow overflowed {overflow} rows even at "
                    f"the max capacity {cap}; original table restored "
                    "(pathological key distribution)"
                )
            cap = min(cap * 2, self.max_capacity)
        self.capacity = actual
        return len(keys)

    def grow(self, new_capacity: int) -> None:
        """Take the table to at least ``new_capacity`` slots, every
        occupied row in the place the new capacity gives it (home
        buckets and probe chains depend on capacity, so a raw row copy
        would be wrong — same reasoning as the cross-topology
        checkpoint restore).

        One chip's bucket table DOUBLES ON THE DEVICE, as often as it
        takes (`buckettable.grow_rehash`: one streaming split of the
        old rows, then an ordinary insert of the few that lay past a
        full bucket): no row crosses to the host, and where
        `prepare_growth` ran for this capacity nothing compiles either.
        Every other table, and a doubling whose re-homed rows found no
        room, goes through the host (`_rehash_through_host`).

        Crash-safe: the old table is the live one until the new one
        holds every row; a caller that catches a raised error and
        continues keeps exact counts either way."""
        with trace.span("grow.table", cat="device",
                        from_slots=int(self.capacity)) as sp:
            with trace.span("grow.wait_outstanding", cat="device"):
                self.complete_outstanding()
            t0 = time.perf_counter()
            old_capacity = self.capacity
            rows = rehomed = unprepared = host_bytes = 0
            # Table lock taken only AFTER the completes above: a thread
            # mid-complete holds the fold lock and may probe the
            # table, so grabbing the table lock first would deadlock
            # (fold → table is the global order).
            with self._table_lock:
                while (self.capacity < new_capacity
                       and self.capacity * 2 <= self.max_capacity
                       and self._splits_on_device()):
                    ready = self._grow_ready_for == self.capacity * 2
                    split = self._split_table()
                    if split is None:
                        break
                    unprepared += not ready
                    rows, moved = split
                    rehomed += moved
                if (self.capacity < new_capacity
                        or self.capacity == old_capacity):
                    host_bytes = int(self._checkpoint_table().rows.nbytes)
                    unprepared += 1  # its reinsert is shaped by the rows
                    rows = self._rehash_through_host(new_capacity)
            sp.set(rows=rows, to_slots=int(self.capacity))
        self._table_fill = rows
        # A rehash changes the table's capacity/topology: a delta chain
        # replayed onto the pre-grow base would restore the OLD
        # capacity, diverging from what a full save would record — the
        # next checkpoint must anchor.
        self._ckpt_mark_dirty_lost("table grow")
        incr_counter("aggregator", "table_grow")
        # Every growth says all three, 0 included: table bytes that
        # crossed to the host, rows inserted again because they lay
        # past a full bucket, doublings that had to compile.
        incr_counter("grow", "host_bytes", value=float(host_bytes))
        incr_counter("grow", "rehomed_rows", value=float(rehomed))
        incr_counter("grow", "unprepared", value=float(unprepared))
        self._note_load()
        print(
            f"table grown {old_capacity} → {self.capacity} slots "
            f"({rows} rows re-hashed in "
            f"{time.perf_counter() - t0:.2f}s, {rehomed} re-homed, "
            f"{host_bytes} B through the host)",
            file=sys.stderr,
        )

    def prepare_growth(self) -> bool:
        """Make ready every program the doubled table will need, if the
        table is near its growth: called at a round's end, after the
        save, when nothing is in flight. Past ``GROW_PREPARE_AT`` of
        ``grow_at`` (and under the ceiling, once a capacity) the table
        is doubled into a SCRATCH table by the growth's own program,
        and the step (at the last batch's shapes), the two programs of
        a packed save and the membership probe (at the widths seen) are
        run once against the scratch, which is then dropped: jit's
        caches hold the executables, so the growth, the steps after it
        and the next save compile nothing. Costs the doubled table's
        HBM (4.29 GB at ``tableBits`` 26) for the seconds this takes,
        and nothing after. True where it ran."""
        target = self.capacity * 2
        if (self.grow_at <= 0 or target > self.max_capacity
                or self._grow_ready_for == target
                or not self._splits_on_device()
                or self._table_fill
                <= GROW_PREPARE_AT * self.grow_at * self.capacity):
            return False
        import jax

        with trace.span("grow.prepare", cat="device",
                        from_slots=int(self.capacity),
                        to_slots=int(target)) as sp:
            with self._table_lock:
                scratch, _, _ = buckettable.grow_rehash(
                    self.table, max_probes=self.max_probes)
            programs = 1
            if self._step_shapes is not None:
                scratch, out = self._step_program(scratch, *(
                    np.zeros(shape, dtype)
                    for shape, dtype in self._step_shapes))
                _pack_out(out)
                programs += 1
            index_fn, chunk_fn = self._pack_programs()
            _fill, index = index_fn(scratch.rows)
            last = chunk_fn(
                scratch.rows, index, np.int32(0),
                chunk=buckettable.pack_chunk_rows(scratch.rows.shape[0]))
            programs += 2
            for shape, dtype in sorted(self._contains_shapes):
                last = buckettable.contains(
                    scratch, jax.numpy.asarray(np.zeros(shape, dtype)),
                    max_probes=self.max_probes)
                programs += 1
            jax.block_until_ready((scratch, last))
            sp.set(programs=programs)
        self._grow_ready_for = target
        return True

    # -- config ----------------------------------------------------------
    def set_cn_prefixes(self, prefixes: tuple[str, ...]) -> None:
        """The directive's prefixes as the step takes them: ONE shape
        whatever they are, ``uint8[CN_PREFIX_ROWS, K]`` with ``K`` the
        widest window the device serves, so an edit of the directive
        compiles nothing (a first compile of the step is minutes on the
        chip). Unused rows are dead (lengths -1: they match nothing);
        an empty element is a live row of length 0 and matches every
        name, as the reference's ``strings.HasPrefix`` does. A prefix
        longer than ``K`` is compared on its head; head-matching lanes
        route to the exact host lane (pipeline._cn_prefix_match
        "undecidable"), so the device never silently decides on a
        truncated prefix. No prefix at all is the unfiltered step's own
        shape, as it always was."""
        self.cn_prefixes = tuple(prefixes)
        if not prefixes:
            self._prefix_arr = np.zeros((0, 1), np.uint8)
            self._prefix_lens = np.zeros((0, 2), np.int32)
            return
        encoded = [p.encode("utf-8") for p in prefixes]
        k = der_kernel.MAX_FIXED_WINDOW_BYTES
        rows = max(CN_PREFIX_ROWS, 1 << (len(encoded) - 1).bit_length())
        if rows > CN_PREFIX_ROWS:
            print(f"issuerCNFilter has {len(encoded)} prefixes: the ingest "
                  f"step takes {rows} rows of them, not {CN_PREFIX_ROWS} "
                  "(a program of its own)", file=sys.stderr)
        arr = np.zeros((rows, k), np.uint8)
        lens = np.full((rows, 2), -1, np.int32)
        for i, b in enumerate(encoded):
            head = b[:k]
            arr[i, : len(head)] = np.frombuffer(head, np.uint8)
            lens[i] = (len(head), len(b))
        self._prefix_arr, self._prefix_lens = arr, lens

    def _count_filtered(self, ca: int = 0, expired: int = 0,
                        cn: int = 0) -> None:
        """Lanes the filters dropped, counted where the reference counts
        them (``certIsFilteredOut``, ct-fetch.go:44-70): in this
        aggregator's ``metrics`` and in the process's
        ``ct-fetch.certIsFilteredOut.{CA,expired,cn}`` counters, which
        ``DatabaseSink`` keeps for the other backends. Called once a
        lane: by the fold for what the device or the pre-parsed lane
        decided, by the exact host lane for what it decided. Under a CN
        filter ``.cn`` moves on every call, by 0 too, so that "none was
        dropped" reads 0."""
        self._cn_fold.filtered_cn += cn
        self.metrics["filtered_ca"] += ca
        self.metrics["filtered_expired"] += expired
        self.metrics["filtered_cn"] += cn
        if ca:
            incr_counter("ct-fetch", "certIsFilteredOut", "CA",
                         value=float(ca))
        if expired:
            incr_counter("ct-fetch", "certIsFilteredOut", "expired",
                         value=float(expired))
        if cn or self.cn_prefixes:
            incr_counter("ct-fetch", "certIsFilteredOut", "cn",
                         value=float(cn))

    def _filter_fold_done(self, span=None) -> None:
        """The end of one batch's fold (the caller holds the fold lock):
        under a CN filter, what the predicate decided goes to the
        ``filter.`` counters, 0 included, and the batch's drops onto the
        fold's span; without one nothing is emitted."""
        fold, self._cn_fold = self._cn_fold, _FilterFold()
        if not self.cn_prefixes:
            return
        if span is not None:
            span.set(filtered_cn=fold.filtered_cn)
        incr_counter("filter", "cn_passed", value=float(fold.passed))
        incr_counter("filter", "cn_dropped", value=float(fold.dropped))
        incr_counter("filter", "cn_undecidable",
                     value=float(len(fold.undecided)))
        incr_counter("filter", "cn_host_dropped",
                     value=float(fold.host_dropped))

    def _now_hour(self) -> int:
        now = self._fixed_now or datetime.now(timezone.utc)
        return int(now.timestamp()) // 3600

    def grow_verify_totals(self, max_idx: int) -> None:
        """Ensure the verify vectors cover issuer index ``max_idx``
        (registry indices are unbounded; only the device meta word caps
        at MAX_ISSUERS — same policy as the issuer_totals growth in
        ``_host_dedup``). Caller holds the fold lock."""
        if max_idx < self.verify_verified.shape[0]:
            return
        size = max(max_idx + 1, 2 * self.verify_verified.shape[0])
        for name in ("verify_verified", "verify_failed"):
            grown = np.zeros((size,), np.int64)
            old = getattr(self, name)
            grown[: old.shape[0]] = old
            setattr(self, name, grown)

    def verify_counts(self) -> dict[str, tuple[int, int]]:
        """issuerID → (verified, failed), nonzero rows only."""
        out: dict[str, tuple[int, int]] = {}
        nz = np.nonzero(self.verify_verified | self.verify_failed)[0]
        for i in nz:
            i = int(i)
            if i < len(self.registry):
                out[self.registry.issuer_at(i).id()] = (
                    int(self.verify_verified[i]),
                    int(self.verify_failed[i]),
                )
        return out

    # -- filter capture (round 15; spill ring round 19) ------------------
    def enable_filter_capture(self, spill_dir: str = "",
                              spill_mem_bytes: int = 0) -> None:
        """Start retaining first-seen serial bytes per (issuer_idx,
        exp_hour) for filter compilation. Seeds from the host-lane
        sets (their bytes survive checkpoints); device-lane serials
        ingested BEFORE enabling are hashes only and cannot be
        recovered — enabling mid-life on a warm table yields a filter
        covering the capture window, and says so once on stderr.
        Forces ``want_serials`` (capture needs the bytes the count-only
        fast path skips).

        With ``spill_dir`` (the ``filterCaptureSpillDir`` directive)
        the capture is a :class:`SpillCaptureRing`: RSS bounded by
        ``spill_mem_bytes``, overflow spilled to durable segment files
        (checkpoint/merge/build surfaces unchanged — the ring's
        ``items()`` is the dict's). An existing dict capture (e.g. a
        restored checkpoint) is folded into the ring."""
        if spill_dir and not isinstance(self.filter_capture,
                                        SpillCaptureRing):
            ring = SpillCaptureRing(spill_dir,
                                    mem_bytes=spill_mem_bytes)
            seed = (self.filter_capture
                    if self.filter_capture is not None
                    else self.host_serials)
            for key, serials in sorted(seed.items()):
                ring.update(key, sorted(serials))
            self.filter_capture = ring
            # The ring owns content-hash tracking from here on.
            self.filter_capture_hashes = None
        if self.filter_capture is None:
            self.filter_capture = {
                key: set(serials)
                for key, serials in self.host_serials.items()
            }
            self.filter_capture_hashes = {
                key: content_token(serials)[1]
                for key, serials in self.filter_capture.items()
            }
            if self._device_written and self._table_fill_exact() > 0:
                print(
                    "filter capture enabled on a warm table: device-lane "
                    "serials ingested before this point are fingerprints "
                    "only and will be missing from emitted filters",
                    file=sys.stderr,
                )
        self.want_serials = True
        # Capture state changed out-of-band of the dirty log (seeding,
        # ring adoption): segments record capture *additions* only, so
        # the next checkpoint must anchor to carry the new baseline.
        self._ckpt_mark_dirty_lost("capture reconfigured")

    def configure_filter_emission(self, path: str,
                                  fp_rate: float = 0.01,
                                  spill_dir: str = "",
                                  spill_mem_bytes: int = 0,
                                  fmt: str = "") -> None:
        """Emit a filter artifact (``path``) on every checkpoint save,
        compiled from the capture at the target FP rate. ``fmt`` picks
        the artifact format ("" → the CTMR_FILTER_FORMAT default)."""
        self.emit_filter_path = path
        if fp_rate > 0:
            self.filter_fp_rate = float(fp_rate)
        self.filter_fmt = fmt or ""
        self.enable_filter_capture(spill_dir=spill_dir,
                                   spill_mem_bytes=spill_mem_bytes)

    def _capture_serial(self, issuer_idx: int, exp_hour: int,
                        serial: bytes) -> None:
        """Record one first-seen serial (fold paths call this under
        the fold lock; set semantics absorb cross-domain repeats)."""
        cap = self.filter_capture
        if cap is None:
            return
        if isinstance(cap, SpillCaptureRing):
            cap.add((issuer_idx, exp_hour), serial)
        else:
            key = (issuer_idx, exp_hour)
            s = cap.setdefault(key, set())
            if serial not in s:
                s.add(serial)
                h = self.filter_capture_hashes
                if h is not None:
                    h[key] = h.get(key, 0) ^ serial_hash(serial)

    def capture_content_hashes(self) -> Optional[dict]:
        """Exact per-(issuer_idx, expHour) XOR content hashes of the
        filter capture, or None when unavailable (capture off, spilled
        ring, or a restored snapshot that predates hash tracking).
        Callers hold the fold lock, as for the capture itself."""
        cap = self.filter_capture
        if cap is None:
            return None
        if isinstance(cap, SpillCaptureRing):
            return cap.content_hashes()
        if self.filter_capture_hashes is None:
            return None
        return dict(self.filter_capture_hashes)

    # -- incremental checkpoints (CTMRCK02, agg/ckpt.py) -----------------
    def configure_checkpointing(self, mode: str = "",
                                max_chain: int = 0,
                                segment_budget_mb: int = 0) -> None:
        """Pin the checkpoint-plane knobs explicitly (the
        ``checkpointMode``/``ckptMaxChain``/``ckptSegmentBudgetMB``
        directives). Unset values fall through the knob ladder
        (CTMR_* env > platformProfile > default), which also applies
        lazily at the first save when this is never called."""
        self._ckpt_knobs = ckpt.resolve_ckpt(
            mode=mode, max_chain=max_chain,
            segment_budget_mb=segment_budget_mb)

    def _ckpt_resolved(self) -> "ckpt.CkptKnobs":
        if self._ckpt_knobs is None:
            self._ckpt_knobs = ckpt.resolve_ckpt()
        return self._ckpt_knobs

    def _ckpt_record_row(self, issuer_idx: int, exp_hour: int,
                         serial: bytes) -> None:
        """Dirty-log one device-table insert (fold paths, under the
        fold lock). No-op until a save/load arms tracking."""
        if not self._ckpt_track or self._ckpt_dirty_lost:
            return
        self._ckpt_rows.append((issuer_idx, exp_hour, serial))
        self._ckpt_note_bytes(len(serial))

    def _ckpt_record_host(self, issuer_idx: int, exp_hour: int,
                          serial: bytes) -> None:
        """Dirty-log one host-lane first-seen serial (under the fold
        lock; _host_dedup already deduplicated it)."""
        if not self._ckpt_track or self._ckpt_dirty_lost:
            return
        self._ckpt_host_adds.append((issuer_idx, exp_hour, serial))
        self._ckpt_note_bytes(len(serial))

    def _ckpt_note_bytes(self, serial_len: int) -> None:
        self._ckpt_row_bytes += serial_len + ckpt.REC.size
        budget = self._ckpt_resolved().segment_budget_mb << 20
        if self._ckpt_row_bytes > budget:
            # A tick whose churn rivals the corpus gains nothing from
            # a delta; cap the log so memory stays bounded.
            self._ckpt_mark_dirty_lost("segment budget exceeded")

    def _ckpt_note_inserted(self, n: int) -> None:
        if self._ckpt_track and not self._ckpt_dirty_lost:
            self._ckpt_dev_inserted += n

    def _ckpt_mark_dirty_lost(self, why: str) -> None:
        """Poison the dirty log: the next save anchors (full base).
        Recording stops and the log drops immediately — correctness
        never depends on a poisoned log's contents."""
        if not self._ckpt_track or self._ckpt_dirty_lost:
            return
        self._ckpt_dirty_lost = True
        self._ckpt_clear_log()
        incr_counter("ckpt", "dirty_lost")
        print(f"checkpoint dirty log dropped ({why}): next save "
              "writes a full base", file=sys.stderr)

    def _ckpt_clear_log(self) -> None:
        self._ckpt_rows = []
        self._ckpt_host_adds = []
        self._ckpt_row_bytes = 0
        self._ckpt_dev_inserted = 0

    def _ckpt_take_shadow(self) -> dict:
        """Copies of the small O(issuers) structures at a durable
        tick, diffed against at the next segment save. Caller holds
        the fold lock (or is otherwise quiesced)."""
        return {
            "registry_len": len(self.registry),
            "issuer_totals": self.issuer_totals.copy(),
            "verify_verified": self.verify_verified.copy(),
            "verify_failed": self.verify_failed.copy(),
            "crl": {i: set(s) for i, s in sorted(self.crl_sets.items())},
            "dn": {i: set(s) for i, s in sorted(self.dn_sets.items())},
        }

    def _ckpt_arm(self, path: str, base_sha: str, tip_token: str,
                  chain_len: int) -> None:
        """Arm dirty tracking against a durable tick at ``path``."""
        self._ckpt_path = path
        self._ckpt_base_sha = base_sha
        self._ckpt_tip_token = tip_token
        self._ckpt_chain_len = chain_len
        self._ckpt_track = True
        self._ckpt_dirty_lost = False
        self._ckpt_clear_log()
        self._ckpt_shadow = self._ckpt_take_shadow()
        set_gauge("ckpt", "chain_length", value=float(chain_len))

    # -- ingest ----------------------------------------------------------
    def ingest(self, entries: list[tuple[bytes, bytes]]) -> IngestResult:
        """Process (leaf_der, issuer_der) pairs; any count, chunked
        internally to the device batch size."""
        n = len(entries)
        res = IngestResult(
            was_unknown=np.zeros((n,), bool),
            filtered=np.zeros((n,), bool),
            exp_hours=np.zeros((n,), np.int32),
            serials=[None] * n,
            issuer_idx=np.zeros((n,), np.int32),
        )
        for i, (_, issuer_der) in enumerate(entries):
            res.issuer_idx[i] = self.registry.get_or_assign(issuer_der)

        max_len = packing.LENGTH_BUCKETS[-1]
        host_lane_total = 0
        for start in range(0, n, self.batch_size):
            chunk = entries[start : start + self.batch_size]
            device_entries, device_pos, host_pos = [], [], []
            for j, (der, _) in enumerate(chunk):
                if len(der) <= max_len:
                    device_entries.append((der, int(res.issuer_idx[start + j])))
                    device_pos.append(start + j)
                else:
                    host_pos.append(start + j)
            if device_entries:
                self.maybe_grow(incoming=len(device_entries))
            # Fold lock taken AFTER maybe_grow: growth completes the
            # outstanding pendings, whose folds need the same lock.
            with self._fold_lock:
                if device_entries:
                    batch = packing.pack_entries(
                        device_entries, batch_size=self.batch_size
                    )
                    host_pos += self._consume_chunk(batch, device_pos, res)
                host_lane_total += self._host_lanes(
                    host_pos, lambda pos: entries[pos][0], res
                )
        self.metrics["host_lane"] += host_lane_total
        res.host_lane_count = host_lane_total
        with self._fold_lock:
            self._filter_fold_done()
        incr_counter("aggregator", "batches")
        return res

    def ingest_packed(
        self,
        data: np.ndarray,
        length: np.ndarray,
        issuer_idx: np.ndarray,
        valid: np.ndarray,
    ) -> IngestResult:
        """The zero-copy fast path: pre-packed rows (e.g. from the
        native batch decoder) go straight to the device, no per-entry
        Python objects. ``issuer_idx`` are registry indices
        (:meth:`IssuerRegistry.get_or_assign`); invalid lanes are
        ignored. Host-lane fallbacks slice their DER from ``data``.

        Synchronous form: submit + immediate complete. Pipelined
        callers use :meth:`ingest_packed_submit` and defer
        ``complete()`` by ``deviceQueueDepth`` batches."""
        return self.ingest_packed_submit(data, length, issuer_idx,
                                         valid).complete()

    def ingest_packed_submit(
        self,
        data: np.ndarray,
        length: np.ndarray,
        issuer_idx: np.ndarray,
        valid: np.ndarray,
        host_data: Optional[np.ndarray] = None,
    ) -> PendingIngest:
        """Dispatch the device steps for a packed batch WITHOUT reading
        anything back. Returns a :class:`PendingIngest`; until its
        ``complete()`` runs, the device computes while the host is free
        to decode/pack the next batch (SURVEY §2.2 PP row).

        ``data`` may be a device array whose H2D transfer the caller
        already started (overlap with the previous step); pass the
        NumPy rows as ``host_data`` then, so rare host-lane fallbacks
        slice DER bytes without a per-entry D2H read."""
        n = int(data.shape[0])
        if host_data is None:
            host_data = data if isinstance(data, np.ndarray) else None
        if host_data is None:
            raise ValueError(
                "host_data is required when data is a device array"
            )
        self.maybe_grow(incoming=n)
        self._inflight_lanes += n
        res = IngestResult(
            was_unknown=np.zeros((n,), bool),
            filtered=np.zeros((n,), bool),
            exp_hours=np.zeros((n,), np.int32),
            serials=[None] * n,
            issuer_idx=np.asarray(issuer_idx, np.int32).copy(),
        )
        chunks = []
        for start in range(0, n, self.batch_size):
            end = min(start + self.batch_size, n)
            m = end - start
            # Every dispatch says how far short of the batch it was (0
            # for a whole one), so a reader tells "none was short" from
            # "this program does not count them".
            incr_counter("ingest", "partial_batches",
                         value=float(m < self.batch_size))
            incr_counter("ingest", "partial_lanes",
                         value=float(self.batch_size - m))
            if m == self.batch_size:
                batch = packing.PackedBatch(
                    data[start:end], length[start:end],
                    res.issuer_idx[start:end], valid[start:end],
                )
            else:  # pad the tail chunk to the compiled batch shape
                b = self.batch_size
                pdata = np.zeros((b, data.shape[1]), np.uint8)
                pdata[:m] = data[start:end]
                plen = np.zeros((b,), np.int32)
                plen[:m] = length[start:end]
                pidx = np.zeros((b,), np.int32)
                pidx[:m] = res.issuer_idx[start:end]
                pval = np.zeros((b,), bool)
                pval[:m] = valid[start:end]
                batch = packing.PackedBatch(pdata, plen, pidx, pval)
            # The valid positions as one index array: the fold works on
            # arrays, and a list of 65,536 ints a batch is Python
            # objects made here only to be turned back there.
            device_pos = start + np.flatnonzero(
                np.asarray(valid[start:end], bool))
            # lanes in the packed batch correspond 1:1 with positions
            # only when every lane is valid; map explicitly otherwise.
            if len(device_pos) != m:
                lane_of = lambda pos, _s=start: pos - _s  # noqa: E731
            else:
                lane_of = None
            out = self._device_step_packed(batch)  # async dispatch
            chunks.append((batch, device_pos, lane_of, out))
        pending = PendingIngest(self, chunks, res, host_data, length)
        self._outstanding.append(pending)
        return pending

    def complete_outstanding(self) -> None:
        """Fold every un-completed submit into host state (FIFO). Any
        reader of aggregate state (drain, checkpoint) calls this first
        so pipelining can never lose in-flight results. Robust to
        another thread completing (and removing) entries
        concurrently — whoever loses the per-pending race no-ops."""
        while True:
            try:
                pending = self._outstanding[0]
            except IndexError:
                return
            pending.complete()

    # -- pre-parsed ingest lane ------------------------------------------
    def ingest_preparsed(self, sidecar, issuer_idx, valid, host_rows,
                         length) -> IngestResult:
        """Synchronous form of the pre-parsed lane: submit + complete."""
        return self.ingest_preparsed_submit(
            sidecar, issuer_idx, valid, host_rows, length).complete()

    def ingest_preparsed_submit(
        self,
        sidecar,
        issuer_idx: np.ndarray,
        valid: np.ndarray,
        host_rows: np.ndarray,
        length: np.ndarray,
    ) -> PendingPreparsed:
        """Dispatch the walker-free device step for host-extracted
        sidecars (:class:`ct_mapreduce_tpu.native.leafpack.Sidecar`).

        Filter and device-exactness predicates are evaluated HERE, with
        arithmetic mirroring ``pipeline.local_lanes`` line for line —
        they are pure functions of the sidecar, so the device step
        collapses to fingerprint + insert + counts on compact inputs
        (no row bytes ship to the device). ``valid`` lanes whose
        sidecar ``ok`` is 0 take the exact host lane here; the
        AggregatorSink instead strips them from ``valid`` and replays
        them through the device-walker path, which keeps the two lanes
        parity-exact on host-lane spill counts too."""
        from ct_mapreduce_tpu.ops.pipeline import N_PREPARSED_FLAG_CAP

        n = int(len(valid))
        valid = np.asarray(valid, bool)
        issuer_idx = np.asarray(issuer_idx, np.int32).copy()
        ok = sidecar.ok.astype(bool) & valid
        nah = sidecar.not_after_hour
        now_hour = np.int32(self._now_hour())

        # Reference filter precedence (pipeline.local_lanes mirror).
        f_ca = ok & sidecar.is_ca.astype(bool)
        f_expired = ok & ~f_ca & (nah < now_hour)
        if self.cn_prefixes:
            cn_hit, cn_undec0 = self._cn_verdict_np(
                host_rows, sidecar.cn_off, sidecar.cn_len)
            reached = ok & ~f_ca & ~f_expired
            cn_passed = reached & cn_hit
            cn_undec = reached & ~cn_hit & cn_undec0
            f_cn = reached & ~cn_hit & ~cn_undec
        else:
            f_cn = cn_undec = cn_passed = np.zeros_like(ok)
        passed = ok & ~f_ca & ~f_expired & ~f_cn

        hour_off = nah.astype(np.int64) - self.base_hour
        meta_ok = (hour_off >= 0) & (hour_off < packing.META_HOUR_SPAN)
        idx_ok = (issuer_idx >= 0) & (issuer_idx < packing.MAX_ISSUERS)
        boundary_hour = nah == now_hour
        fits = sidecar.serial_len <= packing.MAX_SERIAL_BYTES
        device_exact = fits & meta_ok & idx_ok & ~boundary_hour & ~cn_undec
        insertable = passed & device_exact
        static_host_lane = (valid & ~ok) | (passed & ~device_exact)

        # Serial content window, host-gathered (mirrors
        # gather_serials_rows: bytes past serial_len are zero; lanes
        # whose serial exceeds the window are not insertable).
        s = packing.MAX_SERIAL_BYTES
        serial_bytes = np.zeros((n, s), np.uint8)
        if n:
            cols = sidecar.serial_off[:, None].astype(np.int64) + np.arange(s)
            oob = cols >= host_rows.shape[1]
            np.clip(cols, 0, host_rows.shape[1] - 1, out=cols)
            win = host_rows[np.arange(n)[:, None], cols]
            mask = (np.arange(s)[None, :] < sidecar.serial_len[:, None]) & ~oob
            serial_bytes = np.where(mask, win, 0).astype(np.uint8)

        self.maybe_grow(incoming=n)
        self._inflight_lanes += n
        res = IngestResult(
            was_unknown=np.zeros((n,), bool),
            filtered=np.zeros((n,), bool),
            exp_hours=np.zeros((n,), np.int32),
            serials=[None] * n,
            issuer_idx=issuer_idx,
        )

        # Stack into [K, B] resident chunks for the fused dispatch.
        b = min(self.batch_size, max(n, 1))
        k_chunks = max(1, -(-n // b))
        pad = k_chunks * b - n

        def stk(a, dtype):
            a = np.asarray(a, dtype)
            if pad:
                a = np.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
            return a.reshape((k_chunks, b) + a.shape[1:])

        flag_cap = min(N_PREPARSED_FLAG_CAP, max(64, b // 64), max(b, 1))
        out = self._device_step_preparsed(
            stk(serial_bytes, np.uint8), stk(sidecar.serial_len, np.int32),
            stk(nah, np.int32), stk(issuer_idx, np.int32),
            stk(insertable, bool), flag_cap,
        )
        plan = _PreparsedPlan(
            sidecar=sidecar, issuer_idx=issuer_idx, valid=valid, f_ca=f_ca,
            f_expired=f_expired, f_cn=f_cn, cn_passed=cn_passed,
            cn_undec=cn_undec, passed=passed,
            insertable=insertable, static_host_lane=static_host_lane,
            serial_bytes=serial_bytes, host_rows=host_rows,
            length=np.asarray(length, np.int32), n=n, chunk=b,
            flag_cap=flag_cap,
        )
        pending = PendingPreparsed(self, out, plan, res)
        self._outstanding.append(pending)
        return pending

    def _cn_verdict_np(self, rows: np.ndarray, cn_off: np.ndarray,
                       cn_len: np.ndarray):
        """Host mirror of ``pipeline._cn_prefix_match`` — same arrays,
        same K-byte device window, same dead rows, same "undecidable"
        routing of a truncated prefix and of a name the scan could not
        say (``cn_len`` -1), so the pre-parsed lane spills exactly the
        lanes the walker lane spills (the host could decide long
        prefixes exactly, but then the two lanes would disagree on
        host-lane counts)."""
        prefixes, lens = self._prefix_arr, self._prefix_lens
        k = prefixes.shape[1]
        n = rows.shape[0]
        unsaid = cn_len < 0
        cn_len = np.maximum(cn_len, 0)
        cols = cn_off[:, None].astype(np.int64) + np.arange(k)
        oob = cols >= rows.shape[1]
        np.clip(cols, 0, rows.shape[1] - 1, out=cols)
        window = rows[np.arange(n)[:, None], cols].astype(np.int64)
        inside = (np.arange(k)[None, :] < cn_len[:, None]) & ~oob
        window = np.where(inside, window, 0)
        dev_lens, true_lens = lens[:, 0], lens[:, 1]
        eq = window[:, None, :] == prefixes[None, :, :]
        care = np.arange(k)[None, None, :] < dev_lens[None, :, None]
        full = np.all(eq | ~care, axis=-1) & (dev_lens >= 0)[None, :]
        truncated = (true_lens > dev_lens)[None, :]
        hit = np.any(
            full & (cn_len[:, None] >= dev_lens[None, :]) & ~truncated,
            axis=-1)
        undec = ~hit & (unsaid | np.any(
            full & (cn_len[:, None] >= true_lens[None, :]) & truncated,
            axis=-1))
        return hit, undec

    def _device_step_preparsed(self, serials, serial_len, nah, issuer_idx,
                               insertable, flag_cap: int):
        self._device_written = True
        import jax

        step = (pipeline.ingest_step_preparsed
                if jax.default_backend() == "cpu"
                else pipeline.ingest_step_preparsed_donated)
        with trace.span("device.step_preparsed", cat="device"), \
                self._table_lock:
            self.table, out = step(
                self.table, serials, serial_len, nah, issuer_idx,
                insertable, np.int32(self.base_hour),
                max_probes=self.max_probes, flag_cap=flag_cap,
            )
        return out

    def _fold_preparsed(self, out, plan: _PreparsedPlan,
                        res: IngestResult) -> None:
        """Blocking half of the pre-parsed lane: ONE packed D2H read,
        then a host-side fold mirroring ``_consume_out`` semantics.
        Caller holds the fold lock."""
        n, b, cap = plan.n, plan.chunk, plan.flag_cap
        nb = -(-b // 32)
        sc = plan.sidecar
        P = np.asarray(out.packed)  # the one readback
        k_chunks = P.shape[0]
        # Flag-traffic accounting (the smoke gate asserts O(flagged)):
        # the per-chunk scalar counts + compacted overflow ids are the
        # flag bytes; the was-unknown bitmask and issuer-count vectors
        # are data readback, counted separately.
        incr_counter("ingest", "d2h_flag_bytes",
                     value=float(4 * (2 + cap) * k_chunks))
        incr_counter("ingest", "d2h_readback_bytes", value=float(P.nbytes))

        wu = np.zeros((n,), bool)
        ovf = np.zeros((n,), bool)
        dev_inserted = 0
        counts = np.zeros((P.shape[1] - 2 - nb - cap,), np.int64)
        spill_bits = None
        for k in range(k_chunks):
            row = P[k]
            lo, hi = k * b, min((k + 1) * b, n)
            dev_inserted += int(row[0])
            ovf_count = int(row[1])
            bits = row[2:2 + nb].view(np.uint32)
            lanes = (
                (bits[:, None] >> np.arange(32, dtype=np.uint32)) & 1
            ).astype(bool).reshape(-1)[: hi - lo]
            wu[lo:hi] = lanes
            if ovf_count:
                if ovf_count <= cap:
                    ids = row[2 + nb:2 + nb + ovf_count]
                    ids = ids[ids < (hi - lo)]
                    ovf[lo + ids] = True
                else:
                    # Compacted-flag spill: fall back to the full
                    # overflow bitmask (a second, rare readback).
                    if spill_bits is None:
                        spill_bits = np.asarray(out.overflow_bits)
                        incr_counter("ingest", "d2h_flag_bytes",
                                     value=float(spill_bits.nbytes))
                        incr_counter("ingest", "flag_cap_spill")
                    obits = spill_bits[k]
                    ovf[lo:hi] = (
                        (obits[:, None] >> np.arange(32, dtype=np.uint32))
                        & 1
                    ).astype(bool).reshape(-1)[: hi - lo]
            counts += row[2 + nb + cap:].astype(np.int64)

        f_any = plan.f_ca | plan.f_expired | plan.f_cn
        n_cn = int(plan.f_cn.sum())
        self._count_filtered(ca=int(plan.f_ca.sum()),
                             expired=int(plan.f_expired.sum()), cn=n_cn)
        self._cn_fold.passed += int(plan.cn_passed.sum())
        self._cn_fold.dropped += n_cn
        self._cn_fold.undecided.update(np.flatnonzero(plan.cn_undec).tolist())
        self.metrics["overflow"] += int(ovf.sum())
        self.issuer_totals[: counts.shape[0]] += counts

        hl = plan.static_host_lane | ovf
        keep = plan.passed & ~hl  # == valid & ~hl & ~filtered
        res.filtered[~hl] = f_any[~hl]
        res.exp_hours[keep] = sc.not_after_hour[keep]
        if self.want_serials:
            for p_ in np.nonzero(keep)[0]:
                sb = plan.serial_bytes[
                    p_, : sc.serial_len[p_]].tobytes()
                res.serials[p_] = sb
                if wu[p_]:
                    key = (int(plan.issuer_idx[p_]),
                           int(sc.not_after_hour[p_]))
                    # Dirty-log PRE-guard (see _consume_out): the
                    # device table holds this key either way.
                    self._ckpt_record_row(key[0], key[1], sb)
                    if sb in self.host_serials.get(key, ()):
                        # Cross-encoding guard (see module docstring).
                        wu[p_] = False
                        self.issuer_totals[int(plan.issuer_idx[p_])] -= 1
                    else:
                        res.was_unknown[p_] = True
                        self._capture_serial(key[0], key[1], sb)
        else:
            res.was_unknown[wu] = True
            if dev_inserted:
                self._ckpt_mark_dirty_lost("serial-less fold")
        ksel = np.nonzero(res.was_unknown[:n])[0]
        if ksel.size:
            self._accumulate_metadata_lanes(
                plan.host_rows, ksel, plan.issuer_idx[ksel],
                sc.crldp_off[ksel], sc.crldp_len[ksel],
                sc.issuer_off[ksel], sc.issuer_len[ksel],
            )
        n_valid = int(plan.valid.sum())
        dev_unknown = int(wu.sum())
        dev_known = n_valid - int(hl.sum()) - dev_unknown
        self.metrics["inserted"] += dev_unknown
        self.metrics["known"] += max(dev_known, 0)
        self._table_fill += dev_inserted
        self._ckpt_note_inserted(dev_inserted)
        self._note_load()

        host_pos = [int(p) for p in np.nonzero(hl)[0]]
        host_lane_total = self._host_lanes(
            host_pos,
            lambda pos: plan.host_rows[
                pos, : plan.length[pos]].tobytes(),
            res,
        )
        self.metrics["host_lane"] += host_lane_total
        res.host_lane_count = host_lane_total

    def _consume_chunk(self, batch, device_pos, res, lane_of=None):
        """Run one packed chunk on device and fold the outputs into
        ``res`` at the global positions ``device_pos``. Returns the
        positions that must take the exact host lane."""
        out = self._device_step_packed(batch)
        return self._consume_out(batch, out, device_pos, res, lane_of)

    def _consume_out(self, batch, out, device_pos, res, lane_of=None,
                     host_rows=None):
        """Read back one chunk's device outputs and fold them into
        ``res``; the blocking half of the step. ``host_rows`` is the
        host-resident copy of the full padded rows (by global
        position): metadata windows slice it instead of reading the
        64 MB device batch back."""
        # ONE device read for the twelve small fields (each separate
        # buffer read is its own D2H round trip — see _pack_out).
        # wu/etc. are fresh arrays, so the cross-encoding guard below
        # may flip lanes freely.
        with trace.span("fold.wait_device", cat="fold"):
            P = np.asarray(_pack_out(out))
        flags = P[0]
        hl = (flags & 1) != 0
        wu = ((flags >> 1) & 1) != 0
        f_ca = ((flags >> 2) & 1) != 0
        f_exp = ((flags >> 3) & 1) != 0
        f_cn = ((flags >> 4) & 1) != 0
        ovf = ((flags >> 5) & 1) != 0
        dropped = (((flags >> 6) & 1) != 0
                   if hasattr(out, "dispatch_dropped") else None)
        nah, slen = P[1], P[2]
        dp_off, dp_len, in_off, in_len = P[3], P[4], P[5], P[6]
        f_any = f_ca | f_exp | f_cn
        n_cn = int(f_cn.sum())
        self._count_filtered(ca=int(f_ca.sum()), expired=int(f_exp.sum()),
                             cn=n_cn)
        self._cn_fold.dropped += n_cn
        cn_undec = None
        if out.cn_undecidable is not None:  # a CN filter is configured
            cn_undec = ((flags >> 7) & 1) != 0
            self._cn_fold.passed += int(((flags >> 8) & 1).sum())
        if dropped is not None:  # sharded path: routing-cap spill rate
            spilled = int(dropped.sum())
            self.metrics["dispatch_spill"] += spilled
            # Every batch says both, 0 included: lanes that spilled
            # past the per-(source, destination) quota to the exact
            # host lane, and lanes whose fingerprint crossed the
            # all_to_all to its home shard (a probe overflow was
            # routed before it spilled).
            incr_counter("shard", "dispatch_spill_lanes",
                         value=float(spilled))
            incr_counter("shard", "lanes_routed", value=float(
                int((np.asarray(batch.valid, bool) & ~hl & ~f_any).sum())
                + int(ovf.sum())))
        self.metrics["overflow"] += int(ovf.sum())
        # Device counts are MAX_ISSUERS-long; the host array may have
        # grown past that for registry-overflow issuers (host-lane-only).
        counts = np.asarray(out.issuer_unknown_counts, np.int64)
        self.issuer_totals[: counts.shape[0]] += counts

        # Vectorized fold-in (the per-entry Python loop here was the e2e
        # ingest bottleneck): positions and lanes as index arrays, with
        # per-entry Python only where bytes objects are genuinely needed
        # (serial materialization for PEM trees / the cross-encoding
        # guard — skipped entirely for count-only sinks).
        # True table-fill delta: captured BEFORE the cross-encoding
        # guard below flips any was_unknown lane for reporting — the
        # device inserted those keys regardless, and the load-factor
        # estimate must track slots, not report semantics.
        dev_inserted = int(wu.sum())
        n = len(device_pos)
        pos_arr = np.asarray(device_pos, dtype=np.int64).reshape(n)
        if lane_of is None:
            lanes = np.arange(n, dtype=np.int64)
        else:
            lanes = np.asarray(lane_of(pos_arr), dtype=np.int64)
        hl_l = hl[lanes]
        host_pos = [int(p) for p in pos_arr[hl_l]]
        if cn_undec is not None:
            self._cn_fold.undecided.update(pos_arr[cn_undec[lanes]].tolist())
        okm = ~hl_l
        f_l = f_any[lanes]
        res.filtered[pos_arr[okm]] = f_l[okm]
        keep = okm & ~f_l
        kp, kl = pos_arr[keep], lanes[keep]
        res.exp_hours[kp] = nah[kl]
        if self.want_serials:
            sarr = np.asarray(out.serials)  # the one big field, lazily
            for p_, l_ in zip(kp, kl):
                sb = sarr[l_, : slen[l_]].tobytes()
                res.serials[p_] = sb
                if wu[l_]:
                    # Cross-encoding guard (see module docstring).
                    key = (int(batch.issuer_idx[l_]), int(nah[l_]))
                    # Dirty-log the row PRE-guard: the device inserted
                    # this key whether or not the guard flips the
                    # report, and the delta segment mirrors table
                    # slots, not report semantics.
                    self._ckpt_record_row(key[0], key[1], sb)
                    if sb in self.host_serials.get(key, ()):
                        wu[l_] = False
                        # Keep the running per-issuer gauge consistent
                        # with the corrected report.
                        self.issuer_totals[int(batch.issuer_idx[l_])] -= 1
                    else:
                        res.was_unknown[p_] = True
                        self._capture_serial(key[0], key[1], sb)
        else:
            # Count-only sinks stay on the vectorized path permanently:
            # exact totals are guaranteed by drain()'s batched overlap
            # subtraction, so no per-entry guard (or serial bytes) are
            # needed here. was_unknown may over-report on the
            # pathological host-then-device duplicate; counts cannot.
            res.was_unknown[kp[wu[kl]]] = True
            if dev_inserted:
                # No serial bytes → those inserts cannot be dirty-
                # logged; the next checkpoint must anchor.
                self._ckpt_mark_dirty_lost("serial-less fold")
        ksel = np.where(res.was_unknown[pos_arr])[0]
        if ksel.size:
            lanes_arr = np.asarray(lanes)
            if host_rows is not None:
                rows2d = host_rows
                row_sel = pos_arr[ksel]
                issuers = res.issuer_idx[pos_arr[ksel]]
            else:
                rows2d = np.asarray(batch.data)
                row_sel = lanes_arr[ksel]
                issuers = np.asarray(batch.issuer_idx)[lanes_arr[ksel]]
            lsel = lanes_arr[ksel]
            self._accumulate_metadata_lanes(
                rows2d, row_sel, issuers,
                dp_off[lsel], dp_len[lsel], in_off[lsel], in_len[lsel],
            )
        dev_unknown = int(wu.sum())
        dev_known = len(device_pos) - int(hl.sum()) - dev_unknown
        self.metrics["inserted"] += dev_unknown
        self.metrics["known"] += max(dev_known, 0)
        self._table_fill += dev_inserted
        self._ckpt_note_inserted(dev_inserted)
        self._note_load()
        return host_pos

    def _host_lanes(self, host_pos, der_of, res) -> int:
        """Exact host path for flagged + oversized lanes.

        Two phases so the cross-domain device-membership guard is ONE
        batched ``contains`` probe per chunk (each probe is a dispatch
        plus a D2H read — per-cert probing would erode the pipelining
        the sink provides)."""
        staged = []  # (pos, fields, eh) — lanes that reached dedup
        undecided = self._cn_fold.undecided
        for pos in host_pos:
            fields, x = self._host_filter(
                der_of(pos), int(res.issuer_idx[pos]),
                cn_undecided=pos in undecided)
            if fields is None:
                u, f, eh, sb = x
                res.was_unknown[pos], res.filtered[pos] = u, f
                res.exp_hours[pos], res.serials[pos] = eh, sb
            else:
                staged.append((pos, fields, x))
        flags = self._device_known_flags(
            [(int(res.issuer_idx[pos]), eh, fields.serial)
             for pos, fields, eh in staged]
        )
        for (pos, fields, eh), dk in zip(staged, flags):
            u, f, eh2, sb = self._host_dedup(
                fields, int(res.issuer_idx[pos]), eh, device_known=dk
            )
            res.was_unknown[pos], res.filtered[pos] = u, f
            res.exp_hours[pos], res.serials[pos] = eh2, sb
        return len(host_pos)

    def _step_program(self, table, data, length, issuer_idx, valid):
        """``(table, out)``: the walker step's one program a row shape,
        whichever way the rows came: rows already on the device (the
        pipelined ingest path device_puts them ahead of the dispatch)
        and NumPy rows (a chunk short of the batch, padded on the host;
        the per-entry lane) both go through the donating step, the
        second kind put on the device here. Two wrappers were two
        programs, and the second compiled (minutes, on the chip) at the
        first short chunk. The row buffer is donated — the caller keeps
        a host copy for host-lane slices, so it is dead weight after
        this dispatch and XLA may reuse its HBM. The CPU backend keeps
        the non-donating wrapper for both kinds (its XLA can't alias
        this layout and warns on every dispatch). `prepare_growth` runs
        the same call against its scratch table."""
        import jax

        if _donating_backend():
            step = pipeline.ingest_step_donated
            if not isinstance(data, jax.Array):
                data = jax.device_put(data)
        else:
            step = pipeline.ingest_step
        return step(
            table,
            data,
            length,
            issuer_idx,
            valid,
            np.int32(self._now_hour()),
            np.int32(self.base_hour),
            self._prefix_arr,
            self._prefix_lens,
            max_probes=self.max_probes,
        )

    def _device_step_packed(self, batch):
        self._device_written = True
        arrays = (batch.data, batch.length, batch.issuer_idx, batch.valid)
        self._step_shapes = tuple(
            (tuple(a.shape), np.dtype(a.dtype).str) for a in arrays)
        with trace.span("device.step", cat="device"), self._table_lock:
            self.table, out = self._step_program(self.table, *arrays)
        return out

    def _accumulate_metadata_lanes(self, rows2d, row_sel, issuers,
                                   dp_off, dp_len, in_off, in_len):
        """CRL/DN accumulation for device-unknown lanes, keyed by raw
        byte windows so each distinct encoding is parsed once.

        All arrays are pre-selected to the was-unknown lanes: ``rows2d``
        is a HOST-resident padded-row matrix, ``row_sel`` the row per
        lane, ``issuers``/offsets/lengths aligned with it. Work is
        reduced to one representative lane a distinct ``(issuer, window
        bytes)`` first (:func:`_window_reps`), so per-chunk Python cost
        is O(#distinct issuers/CRL encodings), not O(batch)."""
        n = int(row_sel.shape[0])
        if n == 0:
            return
        with trace.span("fold.metadata", cat="fold", lanes=n) as sp:
            dn_reps, dn_np = _window_reps(
                rows2d, row_sel, issuers, in_off, in_len)
            for i in dn_reps:
                idx = int(issuers[i])
                raw_name = rows2d[
                    row_sel[i], in_off[i] : in_off[i] + in_len[i]].tobytes()
                if (idx, raw_name) not in self._dn_raw_seen:
                    self._dn_raw_seen.add((idx, raw_name))
                    try:
                        rdns, _ = hostder.parse_name(raw_name, 0)
                        dn = hostder.render_dn(rdns)
                        self.dn_sets.setdefault(idx, set()).add(dn)
                    except Exception:
                        pass
            crl_reps, crl_np = _window_reps(
                rows2d, row_sel, issuers, dp_off, dp_len)
            for i in crl_reps:
                if dp_len[i] <= 0:
                    continue
                idx = int(issuers[i])
                raw_dp = rows2d[
                    row_sel[i], dp_off[i] : dp_off[i] + dp_len[i]].tobytes()
                if (idx, raw_dp) not in self._crl_raw_seen:
                    self._crl_raw_seen.add((idx, raw_dp))
                    try:
                        urls = hostder._parse_crldp(raw_dp, 0)
                    except Exception:
                        urls = []
                    self._add_crls(idx, urls)
            # A lane that took the NumPy routine for either window
            # counts once. Both counters move on every fold (by 0 where
            # no lane did), so a reader tells "none fell back" from
            # "this program does not count them".
            fallback = int(np.union1d(dn_np, crl_np).size)
            distinct = int(dn_reps.size + crl_reps.size)
            sp.set(fallback_lanes=fallback, distinct=distinct)
        incr_counter("fold", "meta_lanes", value=float(n))
        incr_counter("fold", "meta_fallback_lanes", value=float(fallback))
        incr_counter("fold", "meta_distinct", value=float(distinct))

    def _add_crls(self, issuer_idx: int, urls: list[str]) -> None:
        """http/https only; ldap silently dropped
        (/root/reference/storage/issuermetadata.go:48-73)."""
        for u in urls:
            try:
                parsed = urlparse(u.strip())
            except ValueError:
                continue
            if parsed.scheme in ("http", "https"):
                self.crl_sets.setdefault(issuer_idx, set()).add(parsed.geturl())

    def _host_filter(self, der: bytes, issuer_idx: int,
                     cn_undecided: bool = False):
        """Tolerant host parse + reference filters. Returns
        ``(fields, exp_hour)`` when the lane reaches dedup, else
        ``(None, (was_unknown, filtered, exp_hour, serial))``.
        ``cn_undecided``: the device (or the pre-parsed lane) left this
        lane's CN verdict to the full parse here."""
        try:
            fields = hostder.parse_cert(der)
        except Exception:
            self.metrics["parse_errors"] += 1
            return None, (False, False, 0, None)
        if fields.is_ca:
            self._count_filtered(ca=1)
            return None, (False, True, 0, None)
        eh = fields.not_after_unix_hour
        # Exact instant compare, like the reference's NotAfter.Before(now)
        # (/root/reference/cmd/ct-fetch/ct-fetch.go:52-55). The device
        # lane handles whole-bucket cases and routes the boundary bucket
        # (expiring this hour) here, so this compare is what decides it.
        now = self._fixed_now or datetime.now(timezone.utc)
        if fields.not_after < now:
            self._count_filtered(expired=1)
            return None, (False, True, 0, None)
        if self.cn_prefixes and not hostder.cn_permitted(
                fields.issuer_cn_bytes, self.cn_prefixes):
            self._count_filtered(cn=1)
            self._cn_fold.host_dropped += cn_undecided
            return None, (False, True, 0, None)
        return fields, eh

    def _device_known_flags(self, items) -> list[bool]:
        """Cross-domain guard, mirror of the device→host check in
        `_consume_out`: a lane can migrate into the host domain over
        time (a cert entering its expiry hour is boundary-routed here;
        a table filling up overflows here), so a serial already counted
        in the DEVICE table must not count again. One batched membership
        probe for the whole chunk, no mutation.

        items: [(issuer_idx, exp_hour, serial_bytes)] → bool per item.
        """
        flags = [False] * len(items)
        if not self._device_written:
            return flags
        cand, fps = [], []
        for j, (issuer_idx, eh, serial) in enumerate(items):
            if (
                len(serial) <= packing.MAX_SERIAL_BYTES
                and 0 <= issuer_idx < packing.MAX_ISSUERS
                and 0 <= eh - self.base_hour < packing.META_HOUR_SPAN
            ):
                cand.append(j)
                fps.append(packing.fingerprint_host(issuer_idx, eh, serial))
        if fps:
            known = self._device_contains(np.array(fps, np.uint32))
            for j, k in zip(cand, known):
                flags[j] = bool(k)
        return flags

    def _host_dedup(self, fields, issuer_idx: int, eh: int,
                    device_known: bool = False):
        """Host-set dedup + metadata accumulation for a filtered lane."""
        key = (issuer_idx, eh)
        bucket = self.host_serials.setdefault(key, set())
        if fields.serial in bucket or device_known:
            self.metrics["known"] += 1
            return False, False, eh, fields.serial
        bucket.add(fields.serial)
        self._ckpt_record_host(issuer_idx, eh, fields.serial)
        self._capture_serial(issuer_idx, eh, fields.serial)
        self.metrics["inserted"] += 1
        if issuer_idx >= self.issuer_totals.shape[0]:
            # Registry-overflow issuers (idx >= MAX_ISSUERS) only ever
            # count here; grow the per-issuer totals to fit them.
            grown = np.zeros(
                (max(issuer_idx + 1, 2 * self.issuer_totals.shape[0]),),
                np.int64)
            grown[: self.issuer_totals.shape[0]] = self.issuer_totals
            self.issuer_totals = grown
        self.issuer_totals[issuer_idx] += 1
        # Metadata for host-lane unknowns.
        self.dn_sets.setdefault(issuer_idx, set()).add(fields.issuer_dn)
        self._add_crls(issuer_idx, fields.crl_distribution_points)
        return True, False, eh, fields.serial

    def _host_exact(self, der: bytes, issuer_idx: int):
        """The exact lane for one cert: filter + batched-of-one guard +
        dedup. Returns (was_unknown, filtered, exp_hour, serial)."""
        fields, x = self._host_filter(der, issuer_idx)
        if fields is None:
            return x
        dk = self._device_known_flags([(issuer_idx, x, fields.serial)])[0]
        return self._host_dedup(fields, issuer_idx, x, device_known=dk)

    # -- drain / report --------------------------------------------------
    def drain(self) -> AggregateSnapshot:
        """Pull device state to host and merge with the host lane —
        the data storage-statistics prints
        (/root/reference/cmd/storage-statistics/storage-statistics.go:28-99)."""
        self.complete_outstanding()
        with self._table_lock:
            _, meta = self._drain_table()
        counts: dict[tuple[str, str], int] = {}
        if meta.size:
            uniq, cnt = np.unique(meta, return_counts=True)
            for m, c in zip(uniq, cnt):
                idx, eh = packing.unpack_meta(int(m), self.base_hour)
                key = self._count_key(idx, eh)
                counts[key] = counts.get(key, 0) + int(c)
        # Host-lane serials that ALSO landed in the device table would
        # double count (host-first-then-device duplicate encodings of
        # one (issuer, serial, expiry) identity — the reference's
        # single SADD set counts once). One batched membership probe
        # finds the overlap; overlapping serials count device-side only.
        items = [
            (idx, eh, sb)
            for (idx, eh), serials in self.host_serials.items()
            for sb in serials
        ]
        overlap: dict[tuple[int, int], int] = {}
        for (idx, eh, _sb), dup in zip(items, self._device_known_flags(items)):
            if dup:
                overlap[(idx, eh)] = overlap.get((idx, eh), 0) + 1
        for (idx, eh), serials in self.host_serials.items():
            n = len(serials) - overlap.get((idx, eh), 0)
            if n <= 0:
                continue
            key = self._count_key(idx, eh)
            counts[key] = counts.get(key, 0) + n
        crls = {
            self.registry.issuer_at(i).id(): set(s) for i, s in self.crl_sets.items()
        }
        dns = {
            self.registry.issuer_at(i).id(): set(s) for i, s in self.dn_sets.items()
        }
        vc = self.verify_counts()
        return AggregateSnapshot(
            counts=counts, crls=crls, dns=dns, total=sum(counts.values()),
            verified={k: v for k, (v, _) in vc.items() if v},
            failed={k: f for k, (_, f) in vc.items() if f},
        )

    def _count_key(self, issuer_idx: int, exp_hour: int) -> tuple[str, str]:
        return (
            self.registry.issuer_at(issuer_idx).id(),
            ExpDate.from_unix_hour(exp_hour).id(),
        )

    # -- checkpoint ------------------------------------------------------
    def save_checkpoint(self, path: str) -> None:
        """Durable aggregate state at ``path``.

        The log cursor itself is checkpointed separately (same contract
        as the reference, /root/reference/storage/types.go:25-42); this
        file makes device state restorable after preemption.

        Two modes (``checkpointMode`` knob, agg/ckpt.py):

        - ``ck01``: every save is the full ``.npz`` snapshot — the
          compatibility path and the restore oracle.
        - ``ck02`` (default): the first save (and any save after the
          dirty log was poisoned, or after ``ckptMaxChain`` segments)
          anchors with a full base; every other epoch tick appends one
          O(churn) CTMRCK02 delta segment and updates the chain
          manifest. Restore replays the chain to the exact state a
          full save would have written.

        A full base holds what the table holds: a bucket table is
        packed on the device and only its occupied slots (20 B each)
        and a byte a bucket are copied out and written, stored as they
        are (``_write_npz``); the table lock is held for the dispatch
        of that pack, not for the copy. ``load_checkpoint`` reads that
        form and the positional, deflated one every earlier writer
        wrote, by what the file says.

        Every file lands via temp + fsync + ``os.replace`` so a crash
        mid-write never corrupts the previous durable tick; segments
        land before the manifest that names them, so a torn tick is
        invisible to the loader.
        """
        # A save a cursor save causes carries that span's ``reason``
        # (exit / savePeriod / fleet); one with none is its caller's
        # own: ct-fetch's at a round's end, an idle fleet tick's.
        with measure("ckpt", "save"), self._save_lock, \
                trace.span("ckpt.save", cat="ckpt") as sp:
            self.complete_outstanding()
            knobs = self._ckpt_resolved()
            wrote_segment = False
            compacting = False
            if (knobs.mode == ckpt.MODE_INCREMENTAL and self._ckpt_track
                    and not self._ckpt_dirty_lost
                    and path == self._ckpt_path):
                if self._ckpt_chain_len >= knobs.max_chain:
                    compacting = True  # mandatory anchor
                else:
                    man = self._ckpt_manifest_for_extend(path, knobs)
                    if man is not None:
                        wrote_segment = self._save_segment(path, man)
            if not wrote_segment:
                self._save_full(path, knobs, compacting=compacting)
            kind, bytes_in, bytes_out = self._save_note
            sp.set(kind=kind, bytes_in=bytes_in, bytes_out=bytes_out)
        incr_counter("ckpt", "bytes_written", value=float(bytes_out))
        # Filter emission runs OUTSIDE the save lock (the checkpoint
        # bytes above are already durable): a multi-second scaled
        # build must not block the fleet-cadence save fan-out or a
        # concurrent checkpoint_now. _emit_lock still serializes
        # overlapping emissions (the build cache is not thread-safe).
        if self.emit_filter_path:
            with self._emit_lock:
                self._emit_filter()

    def _save_full(self, path: str, knobs, compacting: bool = False) -> None:
        """One full ck01 base snapshot (+ fresh manifest in ck02 mode).
        Caller holds the save lock."""
        # Snapshot the host items AND cut the dirty generation under
        # the fold lock: rows folded after this cut stay in the (new)
        # log — they may also land in the .npz below, which is safe
        # because segment replay is insert-if-absent/set-union
        # idempotent; rows folded before the cut are fully inside the
        # .npz. Sorted so host_keys/host_vals land in content order,
        # not fold arrival order (ctmrlint: determinism).
        with self._fold_lock:
            host_items = sorted(
                (idx, eh, b";".join(s.hex().encode()
                                    for s in sorted(serials)))
                for (idx, eh), serials in self.host_serials.items()
            )
            # Arm tracking at the SAME cut: a fold landing during the
            # npz write below records into the (fresh) log, so it is
            # carried by the next segment even when the table readback
            # also caught it — replay is idempotent, omission is not.
            self._ckpt_shadow = self._ckpt_take_shadow()
            self._ckpt_clear_log()
            self._ckpt_track = knobs.mode == ckpt.MODE_INCREMENTAL
            self._ckpt_dirty_lost = False
        directory = os.path.dirname(os.path.abspath(path))
        fd, tmp_path = tempfile.mkstemp(
            prefix=os.path.basename(path) + ".tmp.", dir=directory
        )
        try:
            with os.fdopen(fd, "wb") as fh:
                self._write_npz(fh, host_items)
            os.replace(tmp_path, path)
        except BaseException:
            with contextlib.suppress(OSError):
                os.unlink(tmp_path)
            # Rows folded before the cut above exist nowhere durable
            # now; the next save must anchor, not extend.
            self._ckpt_mark_dirty_lost("base save failed")
            raise
        incr_counter("ckpt", "full_saves")
        written = os.path.getsize(path)
        self._save_note = ("full", int(self._checkpoint_table().rows.nbytes),
                           written)
        if knobs.mode == ckpt.MODE_INCREMENTAL:
            ckpt.kill_point("base-post-rename")
            with trace.span("ckpt.seal", cat="ckpt", bytes=written):
                base_sha = ckpt.file_sha256(path)
                ckpt.write_manifest(path, {
                    "format": ckpt.FORMAT,
                    "baseSha256": base_sha,
                    "maxChain": knobs.max_chain,
                    "chain": [],
                })
                ckpt.cleanup_segments(path)
            self._ckpt_path = path
            self._ckpt_base_sha = base_sha
            self._ckpt_tip_token = base_sha
            self._ckpt_chain_len = 0
            set_gauge("ckpt", "chain_length", value=0.0)
            if compacting:
                incr_counter("ckpt", "compactions")
        else:
            # ck01 compatibility mode: a stale manifest from an earlier
            # ck02 run must never pair with this fresh base. The
            # loader's base-hash check already ignores it; the unlink
            # keeps the directory honest.
            with contextlib.suppress(OSError):
                os.unlink(ckpt.manifest_path(path))
            self._ckpt_track = False

    def _ckpt_manifest_for_extend(self, path: str, knobs):
        """The on-disk manifest this save may append to, or None when
        the durable tip is not the one in memory (files moved by
        another process / a ck01-mode save / a fresh path) — the
        caller anchors instead."""
        try:
            man = ckpt.read_manifest(path)
        except ckpt.CkptError:
            return None
        if man is None:
            # A plain ck01 base we ourselves loaded or wrote can grow
            # a chain: synthesize its empty manifest, provided the
            # bytes on disk really are the base we are tracking.
            if self._ckpt_chain_len:
                return None
            if (not os.path.exists(path)
                    or ckpt.file_sha256(path) != self._ckpt_base_sha):
                return None
            return {"format": ckpt.FORMAT,
                    "baseSha256": self._ckpt_base_sha,
                    "maxChain": knobs.max_chain, "chain": []}
        if man.get("baseSha256") != self._ckpt_base_sha:
            return None
        chain = man.get("chain", [])
        try:
            disk_tip = (chain[-1].get("targetSha256") if chain
                        else man.get("baseSha256"))
        except AttributeError:
            return None
        if (disk_tip != self._ckpt_tip_token
                or len(chain) != self._ckpt_chain_len):
            return None
        return man

    def _save_segment(self, path: str, man: dict) -> bool:
        """Append one CTMRCK02 delta segment for this tick and update
        the manifest. Returns False when the dirty log fails its
        self-check (the caller anchors with a full base instead).
        Caller holds the save lock."""
        with self._fold_lock:
            rows = self._ckpt_rows
            host_adds = self._ckpt_host_adds
            if len(rows) != self._ckpt_dev_inserted:
                self._ckpt_mark_dirty_lost(
                    f"recorded {len(rows)} rows, device inserted "
                    f"{self._ckpt_dev_inserted}")
                return False
            # Shadow first: the decode thread registers issuers under
            # no lock of ours, so one may land between the two reads of
            # the registry. Read in this order it is in this segment
            # and again in the next (replay is idempotent); the other
            # way round it is in neither, and its rows restore to an
            # issuer the registry never had.
            shadow = self._ckpt_take_shadow()
            blob = self._ckpt_segment_blob(rows, host_adds)
            self._ckpt_clear_log()
        if not rows and not host_adds and not self._ckpt_blob_nonempty(blob):
            # Nothing churned since the last durable tick: the chain
            # on disk already restores to exactly this state.
            self._ckpt_shadow = shadow
            self._save_note = ("noop", 0, 0)
            return True
        seq = self._ckpt_chain_len + 1
        data, header = ckpt.encode_segment(
            seq, self._ckpt_tip_token, rows, host_adds, blob)
        try:
            ckpt.write_segment(path, seq, data)
            man = dict(man)
            man["chain"] = list(man.get("chain", [])) + [{
                "seq": seq,
                "file": os.path.basename(ckpt.segment_path(path, seq)),
                "targetSha256": header["targetSha256"],
                "payloadSha256": header["payloadSha256"],
                "bytes": len(data),
                "rows": len(rows) + len(host_adds),
            }]
            ckpt.write_manifest(path, man)
        except BaseException:
            # The log was already cut; its rows exist nowhere durable
            # if this tick didn't land. Anchor next time.
            self._ckpt_mark_dirty_lost("segment write failed")
            raise
        self._ckpt_tip_token = header["targetSha256"]
        self._ckpt_chain_len = seq
        self._ckpt_shadow = shadow
        self._save_note = (
            "segment", header["devRowBytes"] + header["hostRowBytes"],
            len(data))
        incr_counter("ckpt", "segments_written")
        incr_counter("ckpt", "segment_bytes", value=float(len(data)))
        incr_counter("ckpt", "dirty_rows",
                     value=float(len(rows) + len(host_adds)))
        set_gauge("ckpt", "chain_length", value=float(seq))
        return True

    def _ckpt_segment_blob(self, rows, host_adds) -> dict:
        """The non-row diffs of this tick against the last durable
        shadow. Caller holds the fold lock — countAfter must be cut
        at the same instant as the dirty log."""
        sh = self._ckpt_shadow or {
            "registry_len": 0,
            "issuer_totals": np.zeros((0,), np.int64),
            "verify_verified": np.zeros((0,), np.int64),
            "verify_failed": np.zeros((0,), np.int64),
            "crl": {}, "dn": {},
        }

        def vec_diff(cur, old):
            padded = np.zeros((cur.shape[0],), np.int64)
            padded[: old.shape[0]] = old
            nz = np.nonzero(cur != padded)[0]
            return {"len": int(cur.shape[0]),
                    "set": [[int(i), int(cur[i])] for i in nz]}

        def set_adds(cur, old):
            out = []
            for i, s in sorted(cur.items()):
                fresh = s - old.get(i, set())
                if fresh:
                    out.append([int(i), sorted(fresh)])
            return out

        blob = {
            "baseHour": int(self.base_hour),
            "countAfter": int(self._table_fill),
            "registryAdds": self.registry.ids_from(sh["registry_len"]),
            "issuerTotals": vec_diff(self.issuer_totals,
                                     sh["issuer_totals"]),
            "verifyVerified": vec_diff(self.verify_verified,
                                       sh["verify_verified"]),
            "verifyFailed": vec_diff(self.verify_failed,
                                     sh["verify_failed"]),
            "crlAdds": set_adds(self.crl_sets, sh["crl"]),
            "dnAdds": set_adds(self.dn_sets, sh["dn"]),
        }
        tokens = self.capture_content_hashes()
        if tokens is not None:
            dirty = sorted({(int(i), int(e)) for i, e, _ in rows}
                           | {(int(i), int(e)) for i, e, _ in host_adds})
            # Round-20 content tokens for the groups this tick dirtied:
            # a restored run resumes dirty-group filter rebuild (and
            # the replay self-check) from these.
            blob["captureTokens"] = [
                [i, e, format(tokens.get((i, e), 0), "032x")]
                for i, e in dirty]
        return blob

    @staticmethod
    def _ckpt_blob_nonempty(blob: dict) -> bool:
        return bool(blob["registryAdds"] or blob["issuerTotals"]["set"]
                    or blob["verifyVerified"]["set"]
                    or blob["verifyFailed"]["set"]
                    or blob["crlAdds"] or blob["dnAdds"])

    def _chain_insert(self, keys: np.ndarray, meta: np.ndarray) -> int:
        """Insert chain-replayed rows into the CURRENT table. The
        device insert kernels are insert-if-absent with accumulating
        counts, so replay is idempotent against rows the base already
        holds (a fold racing a base save may land in both)."""
        return self._bulk_reinsert(keys, meta)

    def _ckpt_replay_segment(self, header, dev_rows, host_rows,
                             blob) -> None:
        """Apply one decoded delta segment on top of the current
        state (base or earlier segments)."""
        base_hour = int(blob.get("baseHour", self.base_hour))
        if base_hour != self.base_hour:
            raise ckpt.CkptError(
                f"segment baseHour {base_hour} != base {self.base_hour}")
        # Registry first: replayed rows may reference issuers the base
        # predates.
        for iid in blob.get("registryAdds", []):
            self.registry.assign_issuer(Issuer.from_string(iid))
        if dev_rows:
            n = len(dev_rows)
            idx = np.array([r[0] for r in dev_rows], np.int64)
            eh = np.array([r[1] for r in dev_rows], np.int64)
            slen = np.array([len(r[2]) for r in dev_rows], np.int32)
            sarr = np.zeros((n, packing.MAX_SERIAL_BYTES), np.uint8)
            for i, (_, _, sb) in enumerate(dev_rows):
                sarr[i, : len(sb)] = np.frombuffer(sb, np.uint8)
            keys = packing.fingerprints_np(idx, eh, sarr, slen)
            off = eh - self.base_hour
            if (off < 0).any() or (off >= packing.META_HOUR_SPAN).any():
                raise ckpt.CkptError("segment exp hour outside meta span")
            meta = ((idx << packing.META_HOUR_BITS) | off).astype(np.uint32)
            overflow = self._chain_insert(keys, meta)
            if overflow:
                raise ckpt.CkptError(
                    f"segment replay overflowed {overflow} rows "
                    f"(base capacity {self.capacity})")
            self._device_written = True
        for i_, e_, sb in dev_rows:
            self._capture_serial(int(i_), int(e_), sb)
        for i_, e_, sb in host_rows:
            key = (int(i_), int(e_))
            self.host_serials.setdefault(key, set()).add(sb)
            self._capture_serial(key[0], key[1], sb)
        for name, field in (("issuerTotals", "issuer_totals"),
                            ("verifyVerified", "verify_verified"),
                            ("verifyFailed", "verify_failed")):
            self._ckpt_apply_vec(field, blob.get(name))
        for i, urls in blob.get("crlAdds", []):
            self.crl_sets.setdefault(int(i), set()).update(urls)
        for i, names in blob.get("dnAdds", []):
            self.dn_sets.setdefault(int(i), set()).update(names)
        # Self-checks: the replayed table must hold exactly the row
        # count the writer saw at this tick, and the capture groups
        # must hash to the writer's round-20 content tokens.
        self._table_fill = self._table_fill_exact()
        want = blob.get("countAfter")
        if want is not None and int(want) != self._table_fill:
            raise ckpt.CkptError(
                f"segment replay count {self._table_fill} != "
                f"recorded {want}")
        tokens = self.capture_content_hashes()
        if tokens is not None:
            for i, e, hx in blob.get("captureTokens", []):
                got = format(tokens.get((int(i), int(e)), 0), "032x")
                if got != hx:
                    raise ckpt.CkptError(
                        f"capture content token mismatch for group "
                        f"({i}, {e}) after replay")

    def _ckpt_apply_vec(self, field: str, spec) -> None:
        """Apply one {len, set: [[idx, value], ...]} vector diff —
        absolute values at changed indices, so replay in chain order
        converges regardless of how many segments touch an index."""
        if not spec:
            return
        vec = getattr(self, field)
        m = int(spec.get("len", vec.shape[0]))
        if m > vec.shape[0]:
            grown = np.zeros((m,), np.int64)
            grown[: vec.shape[0]] = vec
            vec = grown
        for i, v in spec.get("set", []):
            vec[int(i)] = int(v)
        setattr(self, field, vec)

    def _emit_filter(self) -> None:
        """Checkpoint-time filter emission: compile the capture into
        the versioned artifact (filter/artifact.py) and write it
        atomically next to the snapshot. An emission failure must not
        poison the checkpoint that already landed — it is reported and
        counted, and the next checkpoint retries."""
        from ct_mapreduce_tpu.filter import artifact as fartifact
        from ct_mapreduce_tpu.filter.cache import GroupBuildCache

        try:
            if self._filter_build_cache is None:
                self._filter_build_cache = GroupBuildCache()
            art = fartifact.build_from_aggregator(
                self, fp_rate=self.filter_fp_rate,
                fmt=self.filter_fmt or None,
                cache=self._filter_build_cache)
            fartifact.write_artifact(self.emit_filter_path, art.to_bytes())
        except Exception as err:
            incr_counter("filter", "emit_error")
            print(f"filter emission failed ({self.emit_filter_path}): "
                  f"{type(err).__name__}: {err}", file=sys.stderr)

    @staticmethod
    def _copy_off_device(arr) -> np.ndarray:
        """A host-owned copy of a whole device array: what a full save
        of a table that cannot be packed falls back to (and how the
        per-bucket fills, a byte a bucket, come out). One chip: the
        fetch and a copy of it (``np.asarray`` of a CPU-backend array
        is a view of the XLA buffer). Row-sharded over a mesh: every
        shard's transfer is started, then each is written into its
        place in one buffer, so each shard comes off its own chip and
        the array is gathered on the host, once."""
        shards = arr.addressable_shards
        if len(shards) == 1:
            return np.array(arr, copy=True)
        out = np.empty(arr.shape, arr.dtype)
        for shard in shards:
            shard.data.copy_to_host_async()
        for shard in shards:
            out[shard.index] = np.asarray(shard.data)
        return out

    def _pack_programs(self):
        """``(index, chunk)``: the two programs that pack this
        aggregator's bucket table where it lives (one chip here; the
        mesh runs the same two under ``shard_map``, a shard a chip)."""
        return buckettable.pack_index_jit, buckettable.pack_chunk_jit

    def _pack_dispatch(self, table, count: np.ndarray):
        """Dispatch the packing of a bucket table: ``(fill, totals,
        chunks)`` still on the device, or None where the cached fills
        do not add up to ``count`` (a table whose fill words cannot be
        trusted is copied out whole). Caller holds the table lock, and
        may release it on return: the packed chunks are buffers of the
        save's own, not the table a later step donates, and the
        programs that read the table are enqueued ahead of that step.
        The number of chunks follows the occupied count; the program
        that makes one is the same for every chunk of every save."""
        index_fn, chunk_fn = self._pack_programs()
        fill, index = index_fn(table.rows)
        totals = np.asarray(index[-1]).reshape(count.size, -1)[:, -1]
        if not np.array_equal(totals, count.reshape(-1)):
            return None
        per_chunk = buckettable.pack_chunk_rows(
            table.rows.shape[0] // count.size)
        chunks = [
            chunk_fn(table.rows, index, np.int32(c * per_chunk),
                     chunk=per_chunk)
            for c in range(-(-int(totals.max(initial=0)) // per_chunk))]
        return fill, totals.astype(np.int64), chunks

    def _pack_copy_out(self, fill, totals, chunks):
        """The packed table off the device: ``(fill uint8[buckets],
        keys uint32[occupied, 4], meta uint32[occupied], transfers)``.
        Every chunk a shard has rows in is started on its way at once
        and freed as it lands; a shard's rows follow the shard before
        it, so the whole is in the table's bucket order."""
        base = np.concatenate([[0], np.cumsum(totals)])
        keys = np.empty((int(base[-1]), 4), np.uint32)
        meta = np.empty((int(base[-1]),), np.uint32)
        wanted = []
        for c, chunk in enumerate(chunks):
            shards = sorted(chunk.addressable_shards,
                            key=lambda sh: sh.index[0].start or 0)
            per_chunk = chunk.shape[0] // (5 * len(shards))
            for s, shard in enumerate(shards):
                take = int(min(per_chunk, totals[s] - c * per_chunk))
                if take > 0:
                    shard.data.copy_to_host_async()
                    wanted.append(
                        (int(base[s]) + c * per_chunk, take, shard.data))
        chunks.clear()
        transfers = len(wanted)
        wanted.reverse()
        while wanted:
            lo, take, data = wanted.pop()
            words = np.asarray(data).reshape(5, -1)  # word-major
            keys[lo:lo + take] = words[:4, :take].T
            meta[lo:lo + take] = words[4, :take]
        return self._copy_off_device(fill), keys, meta, transfers

    def _write_npz(self, fh, host_items) -> None:
        """The whole snapshot into ``fh``, flushed and synced: the
        table's contents off the device (``ckpt.d2h``), then the write
        (``ckpt.write``).

        A bucket table leaves the device PACKED: under the table lock
        the save only dispatches the programs that write every bucket's
        fill and the occupied slots' five words, densely and in bucket
        order, into buffers of its own (``_pack_dispatch``); the
        copy-out of ``occupied x 20 B`` and a byte a bucket happens
        after the lock is released. The file then holds ``fill``
        beside ``keys`` / ``meta`` of the occupied slots, all three
        stored as they are (fingerprints are SHA-256 output: deflate
        buys nothing and is slowest on them). A layout that does not
        fill contiguously (``CTMR_TABLE=open``), or a bucket table
        whose cached fills disagree with its count, is copied out
        whole under the lock and written positionally and deflated, as
        every base was before; ``ckpt.base_unpacked`` counts those."""
        shards = self._topology_shards()
        with trace.span("ckpt.d2h", cat="ckpt") as sp:
            with self._table_lock:
                table = self._checkpoint_table()
                count = np.array(table.count)
                bucket = isinstance(table, buckettable.BucketTable)
                packed = self._pack_dispatch(table, count) if bucket else None
                if packed is None:
                    # A HOST-OWNED copy made under the lock: np.asarray
                    # of a CPU-backend jax array is a zero-copy VIEW of
                    # the XLA buffer, and the long write below must not
                    # read device memory whose lifetime it doesn't own
                    # (a step may donate the table).
                    rows = self._copy_off_device(table.rows)
            if packed is not None:
                fill, keys, meta, transfers = self._pack_copy_out(*packed)
                table_members = {"fill": fill, "keys": keys, "meta": meta}
                capacity = fill.shape[0] * buckettable.SLOTS
            else:
                slots = (rows[:, : buckettable.SLOTS * 5].reshape(-1, 5)
                         if bucket else rows)
                table_members = {"keys": slots[:, :4], "meta": slots[:, 4]}
                capacity, transfers = slots.shape[0], shards
            sp.set(bytes=sum(int(a.nbytes) for a in table_members.values()),
                   shards=shards, occupied=int(count.sum()),
                   capacity=int(capacity), chunks=transfers)
        incr_counter("ckpt", "base_unpacked", value=float(packed is None))
        layout = "bucket" if bucket else "open"
        if shards > 1:
            # How evenly the key hash fills the shards, at every full
            # save: the emptiest and the fullest shard's occupied slots
            # and the mean over the shards.
            set_gauge("shard", "fill_min", value=float(count.min()))
            set_gauge("shard", "fill_max", value=float(count.max()))
            set_gauge("shard", "fill_mean", value=float(count.mean()))
        extra = {}
        if self.filter_capture is not None:
            # Filter capture rides the checkpoint ONLY when the feature
            # is on (round-15 interplay contract: emitFilter off leaves
            # the .npz byte-identical to pre-round-15 writers). Same
            # hex-joined encoding as the host-lane sets; sorted keys so
            # identical captures serialize identically.
            f_items = sorted(
                (idx, eh, b";".join(s.hex().encode()
                                    for s in sorted(serials)))
                for (idx, eh), serials in self.filter_capture.items()
            )
            extra["filter_keys"] = np.array(
                [(i, e) for i, e, _ in f_items], dtype=np.int64
            ).reshape(-1, 2)
            extra["filter_vals"] = np.array(
                [v for _, _, v in f_items], dtype=object)
            # Exact content hashes ride along when the capture layer
            # has them (row-aligned with filter_keys) so a restored
            # run resumes incremental dirty tracking without an
            # O(capture) rehash. Absent when exactness was lost (e.g.
            # a spilled ring) — restore recomputes instead.
            hashes = self.capture_content_hashes()
            if hashes is not None:
                extra["filter_hashes"] = np.array(
                    [format(hashes.get((i, e), 0), "032x").encode()
                     for i, e, _ in f_items], dtype=object)
        with trace.span("ckpt.write", cat="ckpt") as sp:
            ckpt.write_npz(fh, dict(
                # `layout` records how the table's members map back to
                # a table structure (bucket i//SLOTS vs open-addressed
                # chains) and `n_shards` the key-routing topology; a
                # `fill` member says the base is packed (keys / meta are
                # the occupied slots in bucket order) and its absence
                # that they are positional, one row a slot, as every
                # writer before PR 42 wrote them. Restore rebuilds the
                # same structure, or re-hashes via the reinsertion path
                # (_restore_table / ShardedDedup.bulk_insert_np) when
                # topology or layout differ.
                layout=np.array(layout),
                n_shards=np.int64(shards),
                **table_members,
                count=count,
                registry=np.frombuffer(
                    self.registry.to_json().encode(), dtype=np.uint8
                ),
                base_hour=np.int64(self.base_hour),
                issuer_totals=self.issuer_totals,
                verify_verified=self.verify_verified,
                verify_failed=self.verify_failed,
                host_keys=np.array(
                    [(i, e) for i, e, _ in host_items], dtype=np.int64
                ).reshape(-1, 2),
                host_vals=np.array([v for _, _, v in host_items], dtype=object),
                # json.dumps preserves dict insertion order, so the key
                # iteration must be sorted too or the serialized bytes
                # depend on fold arrival order (ctmrlint: determinism).
                crl_sets=np.frombuffer(
                    json.dumps(
                        {str(k): sorted(v)
                         for k, v in sorted(self.crl_sets.items())}
                    ).encode(),
                    dtype=np.uint8,
                ),
                dn_sets=np.frombuffer(
                    json.dumps(
                        {str(k): sorted(v)
                         for k, v in sorted(self.dn_sets.items())}
                    ).encode(),
                    dtype=np.uint8,
                ),
                **extra,
            ), stored=table_members if packed is not None else ())
            fh.flush()
            os.fsync(fh.fileno())
            sp.set(bytes=fh.tell())

    def _asarray(self, arr: np.ndarray):
        """Checkpoint rows → table-state arrays (device put). The
        host-only snapshot reader overrides this to stay in NumPy."""
        import jax.numpy as jnp

        return jnp.asarray(arr)

    def _unpack_base(self, fill: np.ndarray, keys: np.ndarray,
                     meta: np.ndarray):
        """A packed base's bucket rows, built where the table lives:
        here on the device. The packed stream is put as the file holds
        it (``occupied x 20 B`` and a byte and a running sum a bucket),
        a piece at a time, each put riding beside the unpack of the
        piece before, and ``buckettable.unpack_rows`` builds the rows
        INTO the table this aggregator already has (donated; a table
        of another shape is let go first), so at no instant do two
        tables live on the device, and the pieces are freed as they are
        consumed. The host-only snapshot reader overrides this with
        ``unpack_np``. The fills are checked against the rows
        (``packed_pieces``) before the table is touched or anything is
        put."""
        import jax
        import jax.numpy as jnp

        nb = int(fill.shape[0])
        shape = (nb, buckettable.ROW_WORDS)
        with trace.span("restore.put", cat="ckpt") as sp:
            base, pieces = buckettable.packed_pieces(
                fill, keys, meta, buckettable.unpack_piece_rows(nb))
            rows, self.table = self.table.rows, None
            if rows.shape != shape:
                del rows  # gone before the table of the base's shape is made
                rows = jnp.zeros(shape, jnp.uint32)
            fill_d, base_d = jax.device_put(fill), jax.device_put(base)
            block = min(nb, buckettable.UNPACK_BLOCK)
            sent, count = fill.nbytes + base.nbytes, 0
            for start, lo, hi, keys_piece, meta_piece in pieces:
                rows = buckettable.unpack_rows(
                    rows, fill_d, base_d, jax.device_put(keys_piece),
                    jax.device_put(meta_piece), start, lo, hi, block=block)
                sent += keys_piece.nbytes + meta_piece.nbytes
                count += 1
            sp.set(bytes=int(sent), pieces=count)
        with trace.span("restore.unpack", cat="device",
                        rows=int(keys.shape[0]), buckets=nb, where="device"):
            rows.block_until_ready()
        incr_counter("restore", "host_unpacked", value=0.0)
        return rows

    @staticmethod
    def _occupied_rows(keys, meta, fill):
        """``(keys, meta, capacity)`` for the reinsertion path: a packed
        base's rows are the occupied ones already and its fills say the
        slots it had; a positional base is scanned for them."""
        if fill is not None:
            return keys, meta, int(fill.shape[0]) * buckettable.SLOTS
        occ = keys.any(axis=-1)
        return keys[occ], meta[occ], int(keys.shape[0])

    def _restore_table(self, keys, meta, count, layout: str,
                       ckpt_shards: int, fill=None) -> None:
        """Rebuild table state from a base's table members.

        With ``fill`` the base is packed: ``keys`` / ``meta`` are the
        occupied slots in bucket order, and a matching topology
        rebuilds the very rows they were packed from. Without, they
        are positional (one row a slot, what every earlier writer
        wrote) and a matching topology restores as a raw row copy.
        Either way a snapshot from a different shard count (its slot
        positions encode dest * nb_local + local hash, unreachable by
        this topology's hashes) re-hashes every occupied row through
        the reinsertion path instead — silent positional trust would
        make contains/insert miss those keys and double-count."""
        if ckpt_shards != self._topology_shards():
            keys, meta, ckpt_cap = self._occupied_rows(keys, meta, fill)
            self.capacity = self._rebuild_table(max(ckpt_cap, 1))
            overflow = self._bulk_reinsert(keys, meta)
            if overflow:
                raise RuntimeError(
                    f"checkpoint restore overflowed {overflow} rows "
                    f"re-hashing a {ckpt_shards}-shard snapshot; "
                    f"increase tableBits (capacity {self.capacity})"
                )
            self._table_fill = int(keys.shape[0])
            return
        if layout == "bucket":
            if fill is not None:
                rows = self._unpack_base(fill, keys, meta)
            else:
                slots = hashtable.fuse_rows(keys, meta)
                rows = np.zeros((slots.shape[0] // buckettable.SLOTS,
                                 buckettable.ROW_WORDS), np.uint32)
                rows[:, : buckettable.SLOTS * 5] = slots.reshape(
                    rows.shape[0], -1)
                # The device insert trusts the cached fill word;
                # positional snapshots (and pre-round-5 ones
                # especially) don't carry it.
                buckettable.fill_counts_np(rows)
                rows = self._asarray(rows)
            self.table = buckettable.BucketTable(
                rows=rows, count=self._asarray(count))
            self.capacity = rows.shape[0] * buckettable.SLOTS
        else:
            self.table = hashtable.TableState(
                rows=self._asarray(hashtable.fuse_rows(keys, meta)),
                count=self._asarray(count),
            )
            self.capacity = int(keys.shape[0])

    def load_checkpoint(self, path: str) -> None:
        """Restore from ``path``: the base ``.npz`` plus whatever
        CTMRCK02 delta chain its manifest names. ``resolve_chain``
        hash-validates every link before anything is applied, so a
        torn tick (crash between segment and manifest renames) loads
        as the previous durable state, never a partial one."""
        with trace.span("restore.base", cat="ckpt") as sp:
            with trace.span("restore.verify", cat="ckpt"):
                chain = ckpt.resolve_chain(path)
            self._load_base(path)
            sp.set(rows=self._table_fill,
                   buckets=getattr(self.table, "n_buckets", 0),
                   bytes=os.path.getsize(path))
        for header, dev_rows, host_rows, blob in chain.segments:
            self._ckpt_replay_segment(header, dev_rows, host_rows, blob)
        if chain.segments:
            incr_counter("ckpt", "restore_segments",
                         value=float(len(chain.segments)))
        self._ckpt_arm(path, chain.base_sha, chain.tip_token,
                       len(chain.segments))

    def _load_base(self, path: str) -> None:
        with trace.span("restore.read", cat="ckpt"):
            z = np.load(path, allow_pickle=True)
            keys, meta = np.asarray(z["keys"]), np.asarray(z["meta"])
            count = np.asarray(z["count"])
            # A `fill` member (PR 42 on) says keys / meta are the
            # occupied slots in bucket order; a base without one is
            # positional.
            fill = np.asarray(z["fill"]) if "fill" in z else None
        # The table's members are (keys, meta, count) in every version;
        # `layout` (absent in pre-round-4 snapshots ⇒ open)
        # says how slot positions map back to a table structure, and
        # `n_shards` (absent in pre-round-5 snapshots ⇒ 1) which
        # key-routing topology wrote them. The snapshot's layout wins
        # over CTMR_TABLE: positions are only meaningful in the
        # structure that wrote them.
        layout = str(z["layout"]) if "layout" in z else "open"
        ckpt_shards = int(z["n_shards"]) if "n_shards" in z else 1
        self._restore_table(keys, meta, count, layout, ckpt_shards, fill=fill)
        del keys, meta, fill
        self._device_written = bool(count.sum() > 0)
        self._table_fill = int(count.sum())
        self._inflight_lanes = 0
        self.base_hour = int(z["base_hour"])
        self.registry = IssuerRegistry.from_json(z["registry"].tobytes().decode())
        self.issuer_totals = z["issuer_totals"].copy()
        # Verify vectors are absent in pre-round-13 snapshots → zeros.
        for name in ("verify_verified", "verify_failed"):
            setattr(self, name,
                    z[name].copy() if name in z
                    else np.zeros((packing.MAX_ISSUERS,), np.int64))
        self.host_serials = {}
        for (idx, eh), blob in zip(z["host_keys"], z["host_vals"]):
            serials = {
                bytes.fromhex(h.decode()) for h in blob.split(b";") if h
            }
            self.host_serials[(int(idx), int(eh))] = serials
        self.crl_sets = {
            int(k): set(v)
            for k, v in json.loads(z["crl_sets"].tobytes().decode()).items()
        }
        self.dn_sets = {
            int(k): set(v)
            for k, v in json.loads(z["dn_sets"].tobytes().decode()).items()
        }
        # Filter capture: absent in pre-round-15 snapshots (and any
        # emitFilter-off writer) → capture stays off; a later
        # enable_filter_capture() re-seeds from the restored host sets.
        self.filter_capture = None
        self.filter_capture_hashes = None
        if "filter_keys" in z:
            cap: dict[tuple[int, int], set[bytes]] = {}
            for (idx, eh), blob in zip(
                    z["filter_keys"].reshape(-1, 2), z["filter_vals"]):
                cap[(int(idx), int(eh))] = {
                    bytes.fromhex(h.decode()) for h in blob.split(b";") if h
                }
            self.filter_capture = cap
            if "filter_hashes" in z:
                self.filter_capture_hashes = {
                    (int(idx), int(eh)): int(hx.decode(), 16)
                    for (idx, eh), hx in zip(
                        z["filter_keys"].reshape(-1, 2),
                        z["filter_hashes"])
                }
            else:
                # Pre-hash snapshot (or a writer whose ring had lost
                # exactness): the restored dict IS the full content,
                # so recomputing here regains exact incremental
                # tracking for the rest of the run.
                self.filter_capture_hashes = {
                    key: content_token(serials)[1]
                    for key, serials in cap.items()
                }
            self.want_serials = True


class HostSnapshotAggregator(TpuAggregator):
    """Read-only snapshot consumer for ``storage-statistics --backend=tpu``.

    The report is pure host work (regroup + count + print,
    /root/reference/cmd/storage-statistics/storage-statistics.go:28-99),
    so this subclass keeps the whole table state in NumPy: constructing
    it never allocates device buffers, and a report can run while the
    TPU pool is unavailable. Drain, regroup, and the host/device
    overlap check share the parent's code paths bit for bit — only the
    array residency hooks change.
    """

    def _make_table(self, capacity: int):
        if _table_layout() == "bucket":
            nb = buckettable.bucket_count(
                capacity, max_capacity=self.max_capacity)
            return buckettable.BucketTable(
                rows=np.zeros((nb, buckettable.ROW_WORDS), np.uint32),
                count=np.zeros((), np.int32),
            )
        if capacity & (capacity - 1):
            raise ValueError(f"capacity must be a power of two, got {capacity}")
        return hashtable.TableState(
            rows=np.zeros((capacity, 5), np.uint32),
            count=np.zeros((), np.int32),
        )

    def _asarray(self, arr: np.ndarray):
        return np.asarray(arr)

    def _unpack_base(self, fill: np.ndarray, keys: np.ndarray,
                     meta: np.ndarray):
        """A report process must not claim the device: the rows are
        built in NumPy (``restore.host_unpacked`` counts it)."""
        with trace.span("restore.unpack", cat="ckpt",
                        rows=int(keys.shape[0]), buckets=int(fill.shape[0]),
                        where="host"):
            rows = buckettable.unpack_np(fill, keys, meta)
        incr_counter("restore", "host_unpacked", value=1.0)
        return rows

    def _bulk_reinsert(self, keys: np.ndarray, meta: np.ndarray) -> int:
        """Host-only reinsertion (topology-mismatched snapshots must
        re-hash; a report process must not claim the device to do so)."""
        if not isinstance(self.table, buckettable.BucketTable):
            raise RuntimeError(
                "host-only restore of a topology-mismatched open-layout "
                "snapshot is not supported; restore through a device "
                "aggregator (TpuAggregator/ShardedAggregator) instead")
        rows = np.asarray(self.table.rows)
        ovf = buckettable.bulk_insert_np(
            rows, keys, meta, max_probes=self.max_probes)
        self.table = buckettable.BucketTable(
            rows=rows, count=np.int32(len(keys) - ovf))
        return ovf

    def _chain_insert(self, keys: np.ndarray, meta: np.ndarray) -> int:
        """Chain replay on a host-resident snapshot. bulk_insert_np is
        blind placement (its contract requires keys NOT already in the
        table), but a fold racing a base save can land a row in both
        the base and the following segment — so pre-filter to the
        genuinely-absent keys and accumulate the count instead of
        resetting it like _bulk_reinsert does."""
        if not isinstance(self.table, buckettable.BucketTable):
            raise RuntimeError(
                "host-only chain replay needs the bucket layout; "
                "restore through TpuAggregator/ShardedAggregator")
        rows = np.asarray(self.table.rows)
        _, first = np.unique(keys, axis=0, return_index=True)
        uniq = np.zeros((keys.shape[0],), bool)
        uniq[first] = True
        fresh = uniq & ~buckettable.contains_np(
            rows, keys, max_probes=self.max_probes)
        ovf = buckettable.bulk_insert_np(
            rows, keys[fresh], meta[fresh], max_probes=self.max_probes)
        self.table = buckettable.BucketTable(
            rows=rows,
            count=np.int32(int(np.asarray(self.table.count))
                           + int(fresh.sum()) - ovf))
        return ovf

    # _drain_table is inherited: both layouts' drain_np helpers are
    # pure NumPy over this subclass's host-resident arrays.

    def _device_contains(self, fps: np.ndarray) -> np.ndarray:
        if isinstance(self.table, buckettable.BucketTable):
            return buckettable.contains_np(
                np.asarray(self.table.rows), fps, max_probes=self.max_probes
            )
        return hashtable.contains_np(
            np.asarray(self.table.rows), fps, max_probes=self.max_probes
        )

    def _device_step_packed(self, batch):
        raise RuntimeError(
            "HostSnapshotAggregator is read-only (reports); "
            "use TpuAggregator/ShardedAggregator to ingest")

    def _device_step_preparsed(self, *args, **kwargs):
        raise RuntimeError(
            "HostSnapshotAggregator is read-only (reports); "
            "use TpuAggregator/ShardedAggregator to ingest")
