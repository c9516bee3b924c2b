"""Snapshot isolation for the query plane: epoch-pinned read views.

Queries must not race ingest. The aggregator's table buffer is donated
through every device step (the previous buffer is dead after dispatch)
and its host-lane sets mutate under the fold lock, so a reader that
touched live state mid-step could see a torn table or a half-folded
batch. Instead of per-query locking, the query plane reads an
**immutable epoch-pinned view**: :func:`capture_view` takes the
aggregator's fold lock, then the table lock (the established global
order — see ``TpuAggregator.__init__``), copies the table rows, and
freezes the host-lane serial sets. A device view's copy is made on the
device (:func:`snapshot_copy`, dispatched under the table lock in
front of the next ingest step; the rows never cross the host link); a
host mirror's is the one-fetch read the checkpoint writer uses. Every
query against that view is lock-free and sees one consistent epoch.

Consistency contract (pinned by the threaded stress test in
tests/test_serve.py): any serial whose ingest was **acked** (its
``complete()`` returned) before the view was captured reads as known —
device-lane inserts land in the table at submit time (before the ack)
and host-lane serials fold under the fold lock the capture holds — and
a serial never fed cannot read known (membership is exact, not
probabilistic: the 128-bit fingerprint's false-positive odds are the
same ones the dedup itself already accepts).

Staleness is a bound, not an accident: :class:`ReplicaPool` swaps its
stalest view for a new epoch once it is older than ``max_staleness_s``
and every response carries the view's epoch and age, so a consumer can
tell "known as of 0.3 s ago" from "known as of now".

:class:`ReplicaPool` holds N epoch-pinned views (**device** views in
production) that serve round-robin, refreshed STAGGERED — one replica
swaps to a new epoch at a time, captured and waited for on a
background thread — so a capture (the fold/table locks, which contend
with ingest) never stalls the serving path, and serving itself runs
the jitted ``contains`` kernels on device copies instead of sharing a
host core with ingest's numpy. On a mesh a replica is the row-sharded
copy itself, shard ``i``'s block on shard ``i``'s chip: a batch's
fingerprints are routed on the host by ``shard_of_np`` (the ingest
routing hash) and probed by ONE program over the mesh, each shard
against its own block (``agg.sharded.shard_contains``); on one chip the
pool holds N full copies. Mixed epochs across
replicas are safe by construction: every view is individually
consistent, answers carry the serving view's epoch + age, and
membership is monotone (a serial is never deleted), so an older
replica can only under-report within its surfaced staleness.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
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
    return jax.default_backend() == "tpu"


@jax.jit
def snapshot_copy(rows):
    """A device view's copy of the table rows: one program per table
    shape, reading and writing the whole table in device memory (a
    mesh-sharded table keeps its sharding, shard for shard). The call
    only dispatches it; :meth:`TableView.pin` waits for the result."""
    return jnp.copy(rows)


def _one_chip_state(dev_rows, layout: str):
    """A one-chip copy as a ready probe state (rows + count on the SAME
    device)."""
    state_cls = (buckettable.BucketTable if layout == "bucket"
                 else hashtable.TableState)
    (shard,) = dev_rows.addressable_shards
    return state_cls(shard.data,
                     jax.device_put(np.zeros((), np.int32), shard.device))


class TableView:
    """One immutable epoch of aggregator state, query-ready.

    The dedup table's rows (fused layout rows for either table layout;
    for a sharded aggregator the global row-concatenated array, shard
    ``i`` owning the ``i``-th contiguous block) are held where the view
    probes them: a device view holds ``dev_rows``, the copy
    :func:`capture_view` made on the device, and no host array; a host
    mirror holds ``rows``. ``host_serials`` maps ``(issuer_idx,
    exp_hour)`` to a frozen set of exact-lane serial bytes. Membership
    is the union of the two domains, mirroring the aggregator's own
    cross-domain guards.
    """

    def __init__(
        self,
        epoch: int,
        rows: Optional[np.ndarray],
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
        dev_rows=None,
        created_wall: Optional[float] = None,
        verify_counts: Optional[dict] = None,
        through_entries: int = 0,
    ) -> None:
        self.epoch = epoch
        self.rows = rows  # host mirror; None while the view is on device
        self.n_rows = int((rows if dev_rows is None else dev_rows).shape[0])
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
        self._dev_rows = dev_rows  # the device copy (device views)
        # One chip: the copy as a probe state. A mesh probes the
        # row-sharded copy as it is.
        self._dev_state = (_one_chip_state(dev_rows, layout)
                           if dev_rows is not None and n_shards == 1
                           else None)
        self.replica_ix = None  # pool slot this view serves from
        # Entries folded into the aggregate when the capture held the
        # fold lock: what this view is known to include.
        self.through_entries = int(through_entries)
        # Table bytes this view's capture moved over the host link, in
        # either direction: none for a device view, the rows read out
        # for a host mirror.
        self.host_bytes = 0 if rows is None else int(rows.nbytes)

    @property
    def _device(self) -> bool:
        """Whether the view answers from its device copy."""
        return self._dev_rows is not None

    def age_s(self) -> float:
        return max(0.0, time.time() - self.created_wall)

    def pin(self) -> "TableView":
        """Wait for the device copy NOW, on the caller's (refresh)
        thread, so the serving path never waits for it. The view was
        given its copy at capture, on the device: there is nothing to
        transfer. On a TPU backend a copy that cannot land is an error
        and propagates: the host mirror is what ``serveDevice = false``
        selects, not something a chip run slides into. On other
        backends the view becomes a host mirror permanently — the next
        epoch's capture retries the device path."""
        if self._device:
            try:
                self._dev_rows.block_until_ready()
            except Exception:
                if _on_tpu():
                    raise
                self._to_host_mirror()
        return self

    def _to_host_mirror(self) -> None:
        """Off the TPU only: this view answers from the host from now
        on. The mirror is read from the view's own device copy, which
        is then dropped."""
        incr_counter("serve", "device_fallback")
        self.rows = np.asarray(self._dev_rows)
        self.host_bytes += int(self.rows.nbytes)
        self._dev_rows = None
        self._dev_state = None

    # -- membership ------------------------------------------------------
    def contains_fps(self, fps: np.ndarray) -> np.ndarray:
        """bool[n] membership of fingerprint rows ``uint32[n, 4]``
        against the view's table — a host mirror probes in NumPy; a
        device view runs the jitted ``contains`` kernels on its device
        copy, on pow2-padded batches (log-bounded compile shapes)."""
        n = int(len(fps))
        if n == 0 or self.n_rows == 0:
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
        block = self.n_rows // self.n_shards
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
        try:
            with trace.span("serve.contains_device", cat="serve",
                            lanes=int(fps.shape[0])):
                return self._contains_device_pinned(fps)
        except Exception:
            # Off the TPU, a device copy that stops answering (backend
            # teardown mid-run) degrades to the host mirror instead of
            # failing the batch; the next epoch retries the device.
            if _on_tpu():
                raise
            self._to_host_mirror()
            return self._contains_host(fps)

    def _contains_device_pinned(self, fps: np.ndarray) -> np.ndarray:
        if self.n_shards == 1:
            return self._probe_state(self._dev_state, fps)
        # A batch goes to its shards once: routed on the host (the
        # ingest routing hash) into [n_shards, width, 4], width from
        # the lane count alone, then one program over the mesh and one
        # readback, whatever shards the fingerprints hash to.
        from ct_mapreduce_tpu.agg.sharded import (
            route_to_shards,
            shard_contains,
        )

        n = fps.shape[0]
        keys, dest, pos = route_to_shards(fps, self.n_shards)
        with trace.span("qshard.probe", cat="serve", lanes=n,
                        shards=int(np.unique(dest).size),
                        width=int(keys.shape[1]),
                        replica=(-1 if self.replica_ix is None
                                 else int(self.replica_ix))):
            found = shard_contains(self._dev_rows, keys, self.layout,
                                   self.max_probes)
        self._count_probe(calls=1, lanes=n,
                          padded=self.n_shards * keys.shape[1] - n)
        return found[dest, pos]

    @staticmethod
    def _count_probe(calls: int, lanes: int, padded: int) -> None:
        incr_counter("qshard", "device_calls", value=float(calls))
        incr_counter("qshard", "lanes", value=float(lanes))
        incr_counter("qshard", "padded_lanes", value=float(padded))

    def _probe_state(self, state, fps: np.ndarray) -> np.ndarray:
        """Jitted contains against one probe state, pow2-padded
        (min 16) so compile shapes stay log-bounded — the same rule as
        the aggregator's `_device_contains`. Keys are placed on the
        state's device so the kernel never crosses chips."""
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
        on_mesh = self._device and self.n_shards > 1
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
        elif on_mesh:
            self._count_probe(calls=0, lanes=0, padded=0)
        host_lane_hits = 0
        if self.host_serials:
            for p in range(n):
                if not out[p]:
                    bucket = self.host_serials.get((int(idx[p]), int(eh[p])))
                    if bucket is not None and items[p][2] in bucket:
                        out[p] = True
                        host_lane_hits += 1
        if on_mesh:  # the qshard. family: every batch says all five
            incr_counter("qshard", "batches")
            incr_counter("qshard", "host_lane_hits",
                         value=float(host_lane_hits))
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


def capture_view(agg, epoch: int, device: bool = False) -> TableView:
    """Pin one epoch of ``agg`` (TpuAggregator, ShardedAggregator, or
    the host snapshot reader) into an immutable :class:`TableView`.

    Lock order is fold → table, matching every other cross-state reader
    (``grow``, ``drain``): holding the fold lock freezes the host-lane
    sets mid-nothing (folds serialize on it), and the table lock
    guarantees the row read is dispatched against a live, fully-stepped
    buffer.

    A device view takes its rows by :func:`snapshot_copy`, on the
    device: the table lock is held for the DISPATCH only
    (``TpuAggregator._table_lock``'s contract: a device read dispatched
    under it runs in front of the next step's donation) and
    :meth:`TableView.pin` waits for the copy off every lock. A host
    mirror (``device=False``; off the TPU, also a device view whose
    copy cannot be dispatched) reads the rows to host memory under the
    table lock: a single fetch of ``table.rows`` rather than per-field
    property reads."""
    t0 = time.time()
    rows = dev_rows = None
    with agg._fold_lock, trace.span("snapshot.locked", cat="serve"):
        with agg._table_lock, \
                trace.span("snapshot.copy_dispatch", cat="serve"):
            dedup = getattr(agg, "dedup", None)
            if dedup is not None:  # mesh-sharded: global row view
                live = dedup.rows
                layout = dedup.layout
                n_shards = dedup.n_shards
            else:
                live = agg.table.rows
                layout = ("bucket"
                          if isinstance(agg.table, buckettable.BucketTable)
                          else "open")
                n_shards = 1
            if device:
                try:
                    dev_rows = snapshot_copy(live)
                except Exception:
                    if _on_tpu():
                        raise
                    incr_counter("serve", "device_fallback")
            if dev_rows is None:
                rows = np.asarray(live)
        with trace.span("snapshot.host_freeze", cat="serve"):
            host_serials = {k: frozenset(v)
                            for k, v in agg.host_serials.items() if v}
            issuer_totals = agg.issuer_totals.copy()
            crl_counts = {i: len(s) for i, s in agg.crl_sets.items()}
            dn_counts = {i: len(s) for i, s in agg.dn_sets.items()}
            verify_counts = agg.verify_counts()
            table_fill = agg._table_fill
            # Every folded lane ends in exactly one of the three.
            through = (agg.metrics["inserted"] + agg.metrics["known"]
                       + agg.metrics["host_lane"])
    return TableView(
        epoch=epoch, rows=rows, layout=layout, n_shards=n_shards,
        max_probes=agg.max_probes, base_hour=agg.base_hour,
        host_serials=host_serials, issuer_totals=issuer_totals,
        crl_counts=crl_counts, dn_counts=dn_counts, registry=agg.registry,
        table_fill=table_fill,
        capacity=agg.capacity,
        dev_rows=dev_rows,
        created_wall=t0,
        verify_counts=verify_counts,
        through_entries=through,
    )


class ReplicaPool:
    """N epoch-pinned views serving round-robin with STAGGERED refresh
    — the query plane's one manager of views, and its answer to "serve
    and ingest share a core" (round 10).

    Every replica is a full, individually consistent :class:`TableView`
    whose device copy is made at capture time, on the device, and
    waited for by ``pin()`` on the refresh thread, never the serving
    path. ``view()`` hands out replicas round-robin; when the STALEST
    replica outlives ``max_staleness_s`` (or the pool is not yet
    full), one background capture swaps that single replica to a fresh
    epoch — one at a time, so the fold/table-lock cost of a capture
    (the copy's dispatch, and the freeze of the host-lane sets) is
    paid off the serving path, at most one capture contends with
    ingest at any moment, and at most one table copy beyond the pool's
    N is alive (the new replica beside the one it replaces).

    Mixed epochs across replicas are part of the contract, not a race:
    a batch is answered entirely by one replica, carries that replica's
    epoch + age, and membership is monotone — an older replica can only
    under-report within the staleness it surfaces. ``floor_epoch()``
    (the minimum live epoch) is the validity horizon the hot-serial
    cache keys against.

    Placement: a replica's copy lives where the live table lives. On a
    mesh-sharded aggregator it is row-sharded as the live table is, one
    block a chip, so no chip ever holds the full global rows, and a
    batch is probed by one program over the mesh; on one chip the
    pool holds N full copies beside the live table. ``device=False``
    makes every replica a host-numpy mirror (and, off the TPU, a copy
    that fails does the same per view, loudly, via
    ``serve.device_fallback``)."""

    def __init__(self, agg, n_replicas: int = 2,
                 max_staleness_s: float = 1.0, device: bool = True) -> None:
        self._agg = agg
        self.n_replicas = max(1, int(n_replicas))
        self.max_staleness_s = float(max_staleness_s)
        self._device = bool(device)
        self._lock = threading.Lock()  # replica list + counters
        self._refresh_lock = threading.Lock()  # one capture at a time
        self._replicas: list[TableView] = []
        self._rr = 0
        self._epoch = 0
        self._refreshing = False

    @property
    def refresh_in_flight(self) -> bool:
        """True while a capture is running — readers are being served
        the previous views for the capture's full duration, so
        staleness can transiently exceed the bound; this flag (surfaced
        in stats()/healthz) plus the ``serve.snapshot_age_s`` gauge
        make that window observable."""
        return self._refreshing

    def _next_slot(self) -> int:
        """The slot the next capture lands in: the first empty one,
        else the stalest replica's. Caller holds ``_lock``; captures
        are one at a time, so the answer holds until it is adopted."""
        if len(self._replicas) < self.n_replicas:
            return len(self._replicas)
        return min(range(len(self._replicas)),
                   key=lambda i: self._replicas[i].epoch)

    def _capture(self) -> TableView:
        with self._lock:
            self._epoch += 1
            epoch = self._epoch
            slot = self._next_slot()
        # snapshot.capture's self time is the wait for the fold lock.
        with trace.span("serve.snapshot", cat="serve", epoch=epoch), \
                measure("serve", "replica_swap_s"), \
                trace.span("snapshot.capture", cat="serve", epoch=epoch,
                           replica=slot) as cap:
            v = capture_view(self._agg, epoch, device=self._device)
            with trace.span("snapshot.wait_copy", cat="serve"):
                v.pin()  # wait on THIS thread, not the serving path
            cap.set(through_entries=v.through_entries,
                    host_bytes=v.host_bytes, shards=v.n_shards)
        v.replica_ix = slot
        incr_counter("snapshot", "copies")
        incr_counter("snapshot", "host_bytes", value=float(v.host_bytes))
        return v

    def _adopt(self, v: TableView) -> None:
        with self._lock:
            if v.replica_ix == len(self._replicas):
                self._replicas.append(v)
            else:
                self._replicas[v.replica_ix] = v
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
        """Force one staggered swap NOW (synchronous): capture a new
        epoch, wait for it, and replace the stalest replica (or fill an empty
        pool slot). Serving continues on the other replicas meanwhile."""
        with self._refresh_lock:
            return self._refresh_holding_lock()

    def warm(self) -> "ReplicaPool":
        """Fill every pool slot synchronously (set-up, so that a
        timed window never includes a capture)."""
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
