"""Snapshot isolation for the query plane: epoch-pinned read views.

Queries must not race ingest. The aggregator's table buffer is donated
through every device step (the previous buffer is dead after dispatch)
and its host-lane sets mutate under the fold lock, so a reader that
touched live state mid-step could see a torn table or a half-folded
batch. Instead of per-query locking, the query plane reads an
**immutable epoch-pinned view**: :func:`capture_view` takes the
aggregator's fold lock, then the table lock (the established global
order — see ``TpuAggregator.__init__``), copies the table rows to host
memory through the same one-fetch read the checkpoint writer uses, and
freezes the host-lane serial sets. Every query against that view is
lock-free and sees one consistent epoch.

Consistency contract (pinned by the threaded stress test in
tests/test_serve.py): any serial whose ingest was **acked** (its
``complete()`` returned) before the view was captured reads as known —
device-lane inserts land in the table at submit time (before the ack)
and host-lane serials fold under the fold lock the capture holds — and
a serial never fed cannot read known (membership is exact, not
probabilistic: the 128-bit fingerprint's false-positive odds are the
same ones the dedup itself already accepts).

Staleness is a bound, not an accident: :class:`SnapshotManager`
refreshes the view when it is older than ``max_staleness_s`` and every
response carries the view's epoch and age, so a consumer can tell
"known as of 0.3 s ago" from "known as of now".

:class:`ReplicaPool` (round 12) is the production tier of the same
idea: N epoch-pinned **device** views serve round-robin, refreshed
STAGGERED — one replica swaps to a new epoch at a time, captured and
pinned on a background thread — so a capture (the table D2H under the
fold/table locks, which contends with ingest) never stalls the serving
path, and serving itself runs the jitted ``contains`` kernels on
pinned device copies instead of sharing a host core with ingest's
numpy. On a mesh the pool pins **per-shard row blocks**, each on its
shard's own device (queries route by ``shard_of_np`` exactly like
ingest lanes); on one chip it pins N full copies. Mixed epochs across
replicas are safe by construction: every view is individually
consistent, answers carry the serving view's epoch + age, and
membership is monotone (a serial is never deleted), so an older
replica can only under-report within its surfaced staleness.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

import numpy as np

from ct_mapreduce_tpu.core import packing
from ct_mapreduce_tpu.ops import buckettable, hashtable
from ct_mapreduce_tpu.telemetry import trace
from ct_mapreduce_tpu.telemetry.metrics import (
    incr_counter,
    measure,
    set_gauge,
)


def _on_tpu() -> bool:
    """Whether device serving runs on a TPU backend — where a device
    copy that cannot land or answer is an error, never a quiet switch
    to the host mirror."""
    import jax

    return jax.default_backend() == "tpu"


class TableView:
    """One immutable epoch of aggregator state, query-ready.

    ``rows`` is the host copy of the dedup table (fused layout rows for
    either table layout; for a sharded aggregator the global
    row-concatenated array, shard ``i`` owning the ``i``-th contiguous
    block). ``host_serials`` maps ``(issuer_idx, exp_hour)`` to a
    frozen set of exact-lane serial bytes. Membership is the union of
    the two domains, mirroring the aggregator's own cross-domain
    guards.
    """

    def __init__(
        self,
        epoch: int,
        rows: np.ndarray,
        layout: str,
        n_shards: int,
        max_probes: int,
        base_hour: int,
        host_serials: dict,
        issuer_totals: np.ndarray,
        crl_counts: dict,
        dn_counts: dict,
        registry,
        table_fill: int,
        capacity: int,
        device: bool = False,
        devices: Optional[list] = None,
        created_wall: Optional[float] = None,
        verify_counts: Optional[dict] = None,
    ) -> None:
        self.epoch = epoch
        self.rows = rows
        self.layout = layout
        self.n_shards = n_shards
        self.max_probes = max_probes
        self.base_hour = base_hour
        self.host_serials = host_serials
        self.issuer_totals = issuer_totals
        self.crl_counts = crl_counts
        self.dn_counts = dn_counts
        self.registry = registry
        self.table_fill = table_fill
        self.capacity = capacity
        # issuerID → (verified, failed) embedded-SCT verdicts as of
        # this epoch (round 13); empty when the verify lane is off.
        self.verify_counts = verify_counts or {}
        # Anchored at capture START (not completion): any ingest acked
        # before this instant had released the fold lock before the
        # capture acquired it, so it is provably inside the view — and
        # the surfaced staleness errs larger, never smaller.
        self.created_wall = (time.time() if created_wall is None
                             else created_wall)
        self._device = bool(device)
        self._devices = devices  # explicit placement targets (pool mode)
        self._dev_rows = None  # pinned device copy (device mode)
        self._dev_blocks = None  # per-shard pinned states (sharded pool)
        self.replica_ix = None  # pool slot this view serves from

    def age_s(self) -> float:
        return max(0.0, time.time() - self.created_wall)

    def pin(self) -> "TableView":
        """Materialize the device copy NOW, on the caller's (refresh)
        thread, so the serving path never pays the H2D transfer. In a
        sharded pool each shard's contiguous row block is placed on
        its own device — a replica never holds the full global rows on
        any one chip — wrapped as a ready probe state (rows + count on
        the SAME device, so the jitted kernel runs without cross-device
        transfers). On a TPU backend a copy that cannot land is an
        error and propagates: the host mirror is what ``serveDevice =
        false`` selects, not something a chip run slides into. On other
        backends a failure to pin flips the view to the host-numpy
        mirror permanently — the next epoch's capture retries the
        device path."""
        if not self._device:
            return self
        try:
            import jax
            import jax.numpy as jnp

            if self.n_shards > 1 and self._devices:
                block = self.rows.shape[0] // self.n_shards
                state_cls = (buckettable.BucketTable
                             if self.layout == "bucket"
                             else hashtable.TableState)
                blocks = []
                for s in range(self.n_shards):
                    dev = self._devices[s % len(self._devices)]
                    rows = jax.device_put(
                        self.rows[s * block : (s + 1) * block], dev)
                    count = jax.device_put(np.zeros((), np.int32), dev)
                    blocks.append(state_cls(rows, count))
                self._dev_blocks = blocks
            elif self._devices:
                self._dev_rows = jax.device_put(self.rows,
                                                self._devices[0])
            else:
                self._dev_rows = jnp.asarray(self.rows)
        except Exception:
            if _on_tpu():
                raise
            incr_counter("serve", "device_fallback")
            self._device = False
            self._dev_rows = None
            self._dev_blocks = None
        return self

    # -- membership ------------------------------------------------------
    def contains_fps(self, fps: np.ndarray) -> np.ndarray:
        """bool[n] membership of fingerprint rows ``uint32[n, 4]``
        against the pinned table — host NumPy by default; ``device``
        views pin one device copy and run the jitted ``contains``
        kernels on pow2-padded batches (log-bounded compile shapes)."""
        n = int(len(fps))
        if n == 0 or self.rows.shape[0] == 0:
            return np.zeros((n,), bool)
        fps = np.asarray(fps, np.uint32).reshape(n, 4)
        if self._device:
            return self._contains_device(fps)
        with trace.span("serve.contains_host", cat="serve", lanes=n):
            return self._contains_host(fps)

    def _contains_host(self, fps: np.ndarray) -> np.ndarray:
        if self.n_shards == 1:
            if self.layout == "bucket":
                return buckettable.contains_np(
                    self.rows, fps, max_probes=self.max_probes)
            return hashtable.contains_np(
                self.rows, fps, max_probes=self.max_probes)
        # Sharded read view: home shard from the routing hash, then the
        # layout's local probe inside that shard's contiguous row block
        # — the exact addressing the sharded insert used to place the
        # key (one contains_np per occupied shard, not per lane).
        from ct_mapreduce_tpu.agg.sharded import shard_of_np

        dest = shard_of_np(fps, self.n_shards)
        out = np.zeros((fps.shape[0],), bool)
        block = self.rows.shape[0] // self.n_shards
        for s in np.unique(dest):
            sel = dest == s
            local = self.rows[s * block : (s + 1) * block]
            if self.layout == "bucket":
                out[sel] = buckettable.contains_np(
                    local, fps[sel], max_probes=self.max_probes)
            else:
                out[sel] = hashtable.contains_np(
                    local, fps[sel], max_probes=self.max_probes)
        return out

    def _contains_device(self, fps: np.ndarray) -> np.ndarray:
        if self._dev_rows is None and self._dev_blocks is None:
            # Pinned once per view: queries must never touch the live
            # (donated-through) table buffer. pin() flips the view to
            # the host mirror when no device copy can land.
            self.pin()
            if not self._device:
                return self._contains_host(fps)
        try:
            with trace.span("serve.contains_device", cat="serve",
                            lanes=int(fps.shape[0])):
                return self._contains_device_pinned(fps)
        except Exception:
            # Off the TPU, a pinned copy that stops answering (backend
            # teardown mid-run) degrades to the host mirror instead of
            # failing the batch; the next epoch retries the device.
            if _on_tpu():
                raise
            incr_counter("serve", "device_fallback")
            self._device = False
            self._dev_rows = None
            self._dev_blocks = None
            return self._contains_host(fps)

    def _contains_device_pinned(self, fps: np.ndarray) -> np.ndarray:
        import jax.numpy as jnp

        n = fps.shape[0]
        if self._dev_blocks is not None:
            # Shard-routed: home shard on host (the ingest routing
            # hash), then the jitted single-table probe against that
            # shard's pinned block on that shard's device.
            from ct_mapreduce_tpu.agg.sharded import shard_of_np

            dest = shard_of_np(fps, self.n_shards)
            out = np.zeros((n,), bool)
            for s in np.unique(dest):
                sel = dest == s
                out[sel] = self._probe_state(self._dev_blocks[s],
                                             fps[sel])
            return out
        width = max(16, 1 << max(0, (n - 1).bit_length()))
        if width != n:
            fps = np.pad(fps, ((0, width - n), (0, 0)))
        keys = jnp.asarray(fps)
        if self.n_shards > 1:
            from ct_mapreduce_tpu.agg import sharded

            fn = (sharded._contains_global_bucket
                  if self.layout == "bucket" else sharded._contains_global)
            found = fn(self._dev_rows, keys, n_shards=self.n_shards,
                       max_probes=self.max_probes)
        elif self.layout == "bucket":
            found = buckettable.contains(
                buckettable.BucketTable(self._dev_rows,
                                        jnp.zeros((), jnp.int32)),
                keys, max_probes=self.max_probes)
        else:
            found = hashtable.contains(
                hashtable.TableState(self._dev_rows,
                                     jnp.zeros((), jnp.int32)),
                keys, max_probes=self.max_probes)
        return np.asarray(found)[:n]

    def _probe_state(self, state, fps: np.ndarray) -> np.ndarray:
        """Jitted contains against one pinned probe state, pow2-padded
        (min 16) so compile shapes stay log-bounded — the same rule as
        the aggregator's `_device_contains`. Keys are placed on the
        state's device so the kernel never crosses chips."""
        import jax

        n = fps.shape[0]
        width = max(16, 1 << max(0, (n - 1).bit_length()))
        if width != n:
            fps = np.pad(fps, ((0, width - n), (0, 0)))
        dev = next(iter(state.rows.devices()), None)
        keys = jax.device_put(fps, dev)
        fn = (buckettable.contains if self.layout == "bucket"
              else hashtable.contains)
        return np.asarray(fn(state, keys, max_probes=self.max_probes))[:n]

    def lookup(self, items: list) -> np.ndarray:
        """Batch membership: ``items`` is a list of
        ``(issuer_idx, exp_hour, serial_bytes)`` (``issuer_idx`` may be
        ``-1`` for an issuer the registry has never seen). Returns
        bool[n]: known in EITHER dedup domain.

        Device-eligible lanes (serial fits the fingerprint window,
        issuer/hour in meta range — the same predicates that routed
        them to the device at ingest) probe the pinned table through
        the vectorized host fingerprint; every lane additionally checks
        the frozen host-lane set, because overflow/boundary routing
        means the domains can overlap (aggregator module docstring).
        """
        n = len(items)
        out = np.zeros((n,), bool)
        if n == 0:
            return out
        idx = np.fromiter((it[0] for it in items), np.int64, n)
        eh = np.fromiter((it[1] for it in items), np.int64, n)
        slen = np.fromiter((len(it[2]) for it in items), np.int64, n)
        eligible = (
            (idx >= 0)
            & (idx < packing.MAX_ISSUERS)
            & (slen <= packing.MAX_SERIAL_BYTES)
            & (eh - self.base_hour >= 0)
            & (eh - self.base_hour < packing.META_HOUR_SPAN)
        )
        sel = np.nonzero(eligible)[0]
        if sel.size:
            serials = np.zeros((sel.size, packing.MAX_SERIAL_BYTES), np.uint8)
            for j, p in enumerate(sel):
                sb = items[p][2]
                serials[j, : len(sb)] = np.frombuffer(sb, np.uint8)
            fps = packing.fingerprints_np(
                idx[sel], eh[sel], serials, slen[sel])
            out[sel] = self.contains_fps(fps)
        if self.host_serials:
            for p in range(n):
                if not out[p]:
                    bucket = self.host_serials.get((int(idx[p]), int(eh[p])))
                    if bucket is not None and items[p][2] in bucket:
                        out[p] = True
        return out

    # -- metadata --------------------------------------------------------
    def issuer_meta(self, issuer_id: str) -> Optional[dict]:
        """Per-issuer metadata as of this epoch, or None when the
        registry has never seen the issuer."""
        idx = self.registry.index_of_issuer_id(issuer_id)
        if idx is None:
            return None
        total = (int(self.issuer_totals[idx])
                 if idx < self.issuer_totals.shape[0] else 0)
        meta = {
            "issuer": issuer_id,
            "unknown_total": total,
            "crls": int(self.crl_counts.get(idx, 0)),
            "dns": int(self.dn_counts.get(idx, 0)),
        }
        vc = self.verify_counts.get(issuer_id)
        if vc is not None:
            meta["verified"], meta["failed"] = int(vc[0]), int(vc[1])
        return meta


def capture_view(agg, epoch: int, device: bool = False,
                 devices: Optional[list] = None) -> TableView:
    """Pin one epoch of ``agg`` (TpuAggregator, ShardedAggregator, or
    the host snapshot reader) into an immutable :class:`TableView`.

    Lock order is fold → table, matching every other cross-state reader
    (``grow``, ``drain``): holding the fold lock freezes the host-lane
    sets mid-nothing (folds serialize on it), and the table lock
    guarantees the row fetch reads a live, fully-stepped buffer. The
    row read is the checkpoint writer's one-fetch idiom
    (``_write_npz``): a single D2H of ``table.rows`` rather than
    per-field property reads."""
    t0 = time.time()
    with agg._fold_lock:
        with agg._table_lock:
            dedup = getattr(agg, "dedup", None)
            if dedup is not None:  # mesh-sharded: global row view
                rows = np.asarray(dedup.rows)
                layout = dedup.layout
                n_shards = dedup.n_shards
            else:
                layout = ("bucket"
                          if isinstance(agg.table, buckettable.BucketTable)
                          else "open")
                rows = np.asarray(agg.table.rows)
                n_shards = 1
        host_serials = {k: frozenset(v)
                        for k, v in agg.host_serials.items() if v}
        issuer_totals = agg.issuer_totals.copy()
        crl_counts = {i: len(s) for i, s in agg.crl_sets.items()}
        dn_counts = {i: len(s) for i, s in agg.dn_sets.items()}
        verify_counts = agg.verify_counts()
        table_fill = agg._table_fill
    return TableView(
        epoch=epoch, rows=rows, layout=layout, n_shards=n_shards,
        max_probes=agg.max_probes, base_hour=agg.base_hour,
        host_serials=host_serials, issuer_totals=issuer_totals,
        crl_counts=crl_counts, dn_counts=dn_counts, registry=agg.registry,
        table_fill=table_fill,
        capacity=getattr(agg, "capacity", rows.shape[0]),
        device=device,
        devices=devices,
        created_wall=t0,
        verify_counts=verify_counts,
    )


class SnapshotManager:
    """Bounded-staleness view cache: ``view()`` returns the current
    epoch, refreshing (at most one capture in flight — concurrent
    requesters coalesce on the losing side of the lock) once the view
    is older than ``max_staleness_s``. ``refresh()`` forces a new
    epoch, e.g. after a checkpoint restore."""

    def __init__(self, agg, max_staleness_s: float = 1.0,
                 device: bool = False) -> None:
        self._agg = agg
        self.max_staleness_s = float(max_staleness_s)
        self._device = bool(device)
        self._lock = threading.Lock()
        self._view: Optional[TableView] = None
        self._epoch = 0
        self._refreshing = False

    @property
    def refresh_in_flight(self) -> bool:
        """True while a capture is running — readers that raced past
        the staleness check are being served the previous view for the
        capture's full duration, so staleness can transiently exceed
        the bound; this flag (surfaced in stats()/healthz) plus the
        ``serve.snapshot_age_s`` gauge make that window observable."""
        return self._refreshing

    def view(self) -> TableView:
        v = self._view
        if v is not None and v.age_s() <= self.max_staleness_s:
            set_gauge("serve", "snapshot_age_s", value=v.age_s())
            return v
        with self._lock:
            v = self._view  # a concurrent refresher may have won
            if v is not None and v.age_s() <= self.max_staleness_s:
                return v
            return self._refresh_locked()

    def refresh(self) -> TableView:
        with self._lock:
            return self._refresh_locked()

    def _refresh_locked(self) -> TableView:
        self._epoch += 1
        self._refreshing = True
        try:
            with trace.span("serve.snapshot", cat="serve",
                            epoch=self._epoch), \
                    measure("serve", "snapshot_capture_s"):
                v = capture_view(self._agg, self._epoch,
                                 device=self._device)
        finally:
            self._refreshing = False
        self._view = v
        incr_counter("serve", "snapshot_refresh")
        set_gauge("serve", "snapshot_epoch", value=float(self._epoch))
        set_gauge("serve", "snapshot_age_s", value=v.age_s())
        return v

    def stats(self) -> dict:
        v = self._view
        return {
            "snapshot_epoch": v.epoch if v else 0,
            "snapshot_age_s": round(v.age_s(), 6) if v else None,
            "refresh_in_flight": self._refreshing,
        }


class ReplicaPool:
    """N epoch-pinned device views serving round-robin with STAGGERED
    refresh — the query plane's answer to "serve and ingest share a
    core" (BENCHLOG round 10).

    Every replica is a full, individually consistent :class:`TableView`
    pinned on device at capture time (``pin()`` runs on the refresh
    thread, never the serving path). ``view()`` hands out replicas
    round-robin; when the STALEST replica outlives ``max_staleness_s``
    (or the pool is not yet full), one background capture swaps that
    single replica to a fresh epoch — one at a time, so the D2H +
    fold/table-lock cost of a capture is paid off the serving path and
    at most one capture contends with ingest at any moment.

    Mixed epochs across replicas are part of the contract, not a race:
    a batch is answered entirely by one replica, carries that replica's
    epoch + age, and membership is monotone — an older replica can only
    under-report within the staleness it surfaces. ``floor_epoch()``
    (the minimum live epoch) is the validity horizon the hot-serial
    cache keys against.

    Placement: on a mesh-sharded aggregator each replica pins one
    per-shard row block per device (``TableView.pin``'s shard-routed
    mode) so no chip ever holds the full global rows; on one chip the
    pool holds N full pinned copies. ``device=False`` degrades every
    replica to the host-numpy mirror (and any pin failure does the
    same per view, loudly, via ``serve.device_fallback``)."""

    def __init__(self, agg, n_replicas: int = 2,
                 max_staleness_s: float = 1.0, device: bool = True,
                 devices: Optional[list] = None) -> None:
        self._agg = agg
        self.n_replicas = max(1, int(n_replicas))
        self.max_staleness_s = float(max_staleness_s)
        self._device = bool(device)
        self._devices = devices
        self._lock = threading.Lock()  # replica list + counters
        self._refresh_lock = threading.Lock()  # one capture at a time
        self._replicas: list[TableView] = []
        self._rr = 0
        self._epoch = 0
        self._refreshing = False

    @property
    def refresh_in_flight(self) -> bool:
        return self._refreshing

    def _resolve_devices(self) -> Optional[list]:
        if self._devices is None and self._device:
            import jax

            self._devices = list(jax.devices())
        return self._devices or None

    def _capture(self) -> TableView:
        with self._lock:
            self._epoch += 1
            epoch = self._epoch
        with trace.span("serve.snapshot", cat="serve", epoch=epoch), \
                measure("serve", "replica_swap_s"):
            v = capture_view(self._agg, epoch, device=self._device,
                             devices=self._resolve_devices())
            v.pin()  # transfer on THIS thread, not the serving path
        return v

    def _adopt(self, v: TableView) -> None:
        with self._lock:
            if len(self._replicas) < self.n_replicas:
                v.replica_ix = len(self._replicas)
                self._replicas.append(v)
            else:
                stale = min(range(len(self._replicas)),
                            key=lambda i: self._replicas[i].epoch)
                v.replica_ix = stale
                self._replicas[stale] = v
            n = len(self._replicas)
        incr_counter("serve", "replica_refresh")
        set_gauge("serve", "replicas", value=float(n))
        set_gauge("serve", "snapshot_epoch", value=float(v.epoch))

    def _refresh_holding_lock(self) -> TableView:
        self._refreshing = True
        try:
            v = self._capture()
            self._adopt(v)
            return v
        finally:
            self._refreshing = False

    def refresh(self) -> TableView:
        """Force one staggered swap NOW (synchronous): capture + pin a
        new epoch and replace the stalest replica (or fill an empty
        pool slot). Serving continues on the other replicas meanwhile."""
        with self._refresh_lock:
            return self._refresh_holding_lock()

    def warm(self) -> "ReplicaPool":
        """Fill every pool slot synchronously (bench/sweep setup, so
        the timed window never includes a capture)."""
        while True:
            with self._lock:
                if len(self._replicas) >= self.n_replicas:
                    return self
            self.refresh()

    def view(self) -> TableView:
        """One replica, round-robin; triggers a background staggered
        swap when the stalest replica is past the staleness bound. Only
        the very first call (empty pool) captures synchronously."""
        with self._lock:
            reps = list(self._replicas)
            if reps:
                self._rr = (self._rr + 1) % len(reps)
                v = reps[self._rr]
        if not reps:
            with self._refresh_lock:
                with self._lock:
                    if self._replicas:  # lost the first-capture race
                        return self._replicas[0]
                return self._refresh_holding_lock()
        due = (len(reps) < self.n_replicas
               or max(r.age_s() for r in reps) > self.max_staleness_s)
        if due and not self._refreshing:
            self._refresh_async()
        set_gauge("serve", "snapshot_age_s", value=v.age_s())
        return v

    def _refresh_async(self) -> None:
        if not self._refresh_lock.acquire(blocking=False):
            return  # a capture is already in flight
        self._refreshing = True

        def run() -> None:
            try:
                v = self._capture()
                self._adopt(v)
            finally:
                self._refreshing = False
                self._refresh_lock.release()

        threading.Thread(target=run, name="serve-replica-refresh",
                         daemon=True).start()

    def floor_epoch(self) -> int:
        """Minimum epoch across live replicas — the oldest answer the
        round-robin could legally serve, and the hot-serial cache's
        validity horizon."""
        with self._lock:
            return min((r.epoch for r in self._replicas), default=0)

    def stats(self) -> dict:
        with self._lock:
            reps = list(self._replicas)
            refreshing = self._refreshing
        ages = [round(r.age_s(), 6) for r in reps]
        return {
            "replicas": len(reps),
            "replica_target": self.n_replicas,
            "replica_epochs": [r.epoch for r in reps],
            "replica_ages_s": ages,
            "replica_device": [bool(r._device) for r in reps],
            "snapshot_epoch": max((r.epoch for r in reps), default=0),
            "snapshot_age_s": min(ages) if ages else None,
            "refresh_in_flight": refreshing,
        }
