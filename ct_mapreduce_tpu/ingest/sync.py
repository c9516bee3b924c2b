"""The ingest runtime: per-log downloaders feeding store workers.

Rebuilds the reference's ``LogSyncEngine`` / ``LogWorker`` /
``insertCTWorker`` machinery (/root/reference/cmd/ct-fetch/
ct-fetch.go:83-488) on Python threads and a bounded queue:

- one downloader thread per log URL (ct-fetch.go:527-565), fetching
  ranges of 1000 and decoding leaves (ct-fetch.go:398-488);
- a shared entry channel bounded in ENTRIES, whatever the size of its
  items: 16,384 (ct-fetch.go:132) in front of a consumer that takes an
  entry at a time, one device batch where whole get-entries responses
  feed a sink that pauses for a batch (:class:`_EntryChannel`);
- ``num_threads`` store workers draining the queue into a sink
  (ct-fetch.go:140-145,180-246);
- a save ticker checkpointing each log's cursor every ``save_period``
  and at exit (ct-fetch.go:307-312,360-392,472-473);
- graceful stop: signal → downloaders drain → queue drains → workers
  join → final state save (ct-fetch.go:610-620).

Two sinks cover the reference path and the TPU path:

- :class:`DatabaseSink` — per-entry host store through
  ``FilesystemDatabase`` with the ``certIsFilteredOut`` semantics
  (ct-fetch.go:44-70): reference-parity mode.
- :class:`AggregatorSink` — packs entries into device batches for
  :class:`~ct_mapreduce_tpu.agg.aggregator.TpuAggregator`: the
  TPU-native mode, where filtering happens on device.
"""

from __future__ import annotations

import contextlib
import queue
import random
import threading
import time
from collections import deque

import numpy as np
from dataclasses import dataclass, field
from datetime import datetime, timezone
from typing import Optional, Protocol

from ct_mapreduce_tpu.core import der as hostder
from ct_mapreduce_tpu.core.types import CertificateLog
from ct_mapreduce_tpu.ingest.ctclient import (
    BATCH_SIZE,
    CTLogClient,
    short_url,
)
from ct_mapreduce_tpu.ingest.leaf import (
    DecodedEntry,
    LeafDecodeError,
    decode_json_entry,
    leaf_timestamp_ms as decode_leaf_timestamp,
)
from ct_mapreduce_tpu.native.leafpack import EntryPage, StrPage
from ct_mapreduce_tpu.telemetry import flight, metrics, trace

ENTRY_QUEUE_CAPACITY = 16384  # ct-fetch.go:132

def _resolve_verify_lazy(flag, keys_path, window=None, qtable_size=0):
    """Import-light wrapper around ``verify.lane.resolve_verify`` —
    the verify package (and with it the ECDSA kernels) only loads when
    the lane could actually be on."""
    import os

    if flag is None:
        flag = os.environ.get("CTMR_VERIFY", "0") == "1"
    if not flag:
        return False, "", 0, 0, 0
    from ct_mapreduce_tpu.verify.lane import resolve_verify

    return resolve_verify(True, keys_path, window=window,
                          qtable_size=qtable_size)


class EntrySink(Protocol):
    def store(self, entry: DecodedEntry, log_url: str) -> None: ...
    def flush(self) -> None: ...


class DatabaseSink:
    """Per-entry host store: parse → filter → ``database.store``.

    The filter reproduces ``certIsFilteredOut`` (ct-fetch.go:44-70):
    CA certs out, expired out unless ``log_expired_entries``, and when
    CN prefixes are configured, issuers whose CN matches none are out.
    """

    def __init__(
        self,
        database,
        cn_filters: tuple[str, ...] = (),
        log_expired_entries: bool = False,
        now: Optional[datetime] = None,
    ):
        self.database = database
        self.cn_filters = tuple(cn_filters)
        self.log_expired_entries = log_expired_entries
        self._fixed_now = now

    def _filtered_out(self, fields) -> bool:
        if fields.is_ca:
            metrics.incr_counter("ct-fetch", "certIsFilteredOut", "CA")
            return True
        now = self._fixed_now or datetime.now(timezone.utc)
        if not self.log_expired_entries and fields.not_after < now:
            metrics.incr_counter("ct-fetch", "certIsFilteredOut", "expired")
            return True
        if self.cn_filters and not hostder.cn_permitted(
                fields.issuer_cn_bytes, self.cn_filters):
            metrics.incr_counter("ct-fetch", "certIsFilteredOut", "cn")
            return True
        return False

    def store(self, entry: DecodedEntry, log_url: str) -> None:
        try:
            with metrics.measure("ct-fetch", "parseCertificate"):
                fields = hostder.parse_cert(entry.cert_der)
        except Exception:
            # Tolerate-and-skip, like ct-fetch.go:206-215.
            metrics.incr_counter("ct-fetch", "parseCertificateError")
            return
        if self._filtered_out(fields):
            return
        if entry.issuer_der is None:
            metrics.incr_counter("ct-fetch", "noChainError")
            return
        with metrics.measure("ct-fetch", "storeCertificate"):
            self.database.store(
                entry.cert_der, entry.issuer_der, log_url, entry.index
            )
        metrics.incr_counter("ct-fetch", "insertCertificate")

    def flush(self) -> None:
        pass


class AggregatorSink:
    """Batches entries for the device pipeline.

    Entries accumulate host-side until ``flush_size`` and are then
    dispatched in one ``TpuAggregator.ingest`` call (parse, filter,
    fingerprint, dedup and counts all happen on device). A lock
    serializes dispatch — the aggregator's table state is donated
    between steps, so one device stream exists regardless of how many
    store workers feed it.
    """

    PAD_LEN = 2048  # device row width for the raw path (bucket; certs
    # above it take the exact host lane, like oversized serials)

    def __init__(self, aggregator, flush_size: int = 4096, backend=None,
                 device_queue_depth: int = 2, decode_workers: int = 0,
                 preparsed: Optional[bool] = None, decode_threads: int = 0,
                 verify_signatures: Optional[bool] = None,
                 verify_log_keys: Optional[str] = None,
                 verify_precomp_window: Optional[int] = None,
                 verify_qtable_size: int = 0):
        self.aggregator = aggregator
        self.flush_size = flush_size
        # Optional durable backend (certPath): first-seen certs get the
        # same <exp>/<issuer>/<serial> PEM tree + dirty markers the
        # reference writes (filesystemdatabase.go:189-208).
        self.backend = backend
        self._allocated: set[tuple[str, str]] = set()
        self._pem_lock = threading.Lock()  # store workers + per-entry path
        self._pending: list[tuple[bytes, bytes]] = []
        self._pending_raw = _RawChunk()
        self._batch_seq = 0  # raw chunks cut so far; under _lock
        self._lock = threading.Lock()
        # Cuts taken from the accumulators and not yet handed to the
        # device: neither pending nor in flight, so a flush waits for
        # those older than itself (_handing_over). Numbered in the
        # order cut; under _lock.
        self._cut_seq = 0
        self._open_cuts: set[int] = set()
        self._cut_handed = threading.Condition(self._lock)
        self._failed = False  # a failure left its flight dump; under _lock
        self._dispatch_lock = threading.Lock()  # one device stream
        # Host↔device pipelining (deviceQueueDepth, SURVEY §2.2 PP row;
        # the reference overlaps download and store with goroutines + a
        # 16,384-entry channel, ct-fetch.go:132,398-488; here the
        # channel holds one batch of this sink, LogSyncEngine): device steps
        # are SUBMITTED without readback and consumed once more than
        # `device_queue_depth` batches are in flight, so decode of
        # batch N+1 overlaps the device step of batch N. Depth 0 =
        # fully synchronous (reference-exact store ordering).
        self.device_queue_depth = max(0, int(device_queue_depth))
        # 0 = leafpack auto-sizing (CTMR_DECODE_WORKERS / cpu count).
        self.decode_workers = int(decode_workers) or None
        # Intra-chunk native decode threads (`decodeThreads` directive /
        # CTMR_DECODE_THREADS): the persistent C++ worker pool splits
        # each chunk's decode, row pack, and sidecar extraction over
        # lane ranges. 0 = leafpack auto (env, then cpu count). This is
        # the knob that makes ONE chunk's host feed scale with cores.
        self.decode_threads = int(decode_threads) or None
        self._inflight: deque = deque()  # (PendingIngest, der_of)
        # Without a PEM backend the per-entry serial bytes are only
        # needed for the cross-encoding guard; let the aggregator skip
        # materializing them when it can (count-only fast path). A
        # filter capture (round 15) needs the bytes regardless of PEM
        # backing — never clobber its want_serials.
        aggregator.want_serials = (
            backend is not None
            or getattr(aggregator, "filter_capture", None) is not None)
        self.entries_in = 0
        # Pre-parsed ingest lane (CTMR_PREPARSED=1 / preparsedIngest
        # directive): the native decoder's sidecar extraction replaces
        # the on-device DER walk — the device step runs fingerprint +
        # insert + counts on ~59 B/lane of compact inputs, row bytes
        # never ship, and the readback is the compact bitmask/flag-id
        # form. Lanes the extractor flags undecidable (sidecar.ok == 0)
        # replay through the device-walker path, so the two lanes stay
        # parity-exact including host-lane spill counts. Requires the
        # native library; silently stays on the walker lane without it.
        if preparsed is None:
            import os

            preparsed = os.environ.get("CTMR_PREPARSED", "0") == "1"
        self.preparsed = bool(preparsed)
        # Signature-verification lane (round 13): `verifySignatures`
        # directive / CTMR_VERIFY env. Each decoded chunk additionally
        # runs the native SCT extraction pass; P-256-keyed SCTs batch
        # onto the device ECDSA kernel (ops/ecdsa.py) alongside the
        # dedup dispatch, undecidable lanes replay through the pure-
        # python host verifier — the walker-fallback pattern applied
        # to verification. Verdicts fold into the aggregator's per-
        # issuer verified/failed vectors. Off by default: the lane adds
        # an extraction pass + a second kernel family to the hot path.
        # Round 17: `verifyPrecompWindow` (0 = legacy Jacobian ladder)
        # selects the windowed-precompute kernels and `verifyQTableSize`
        # bounds the per-curve device-resident per-log-key Q-table LRU.
        v_on, v_keys, v_batch, v_window, v_qsize = _resolve_verify_lazy(
            verify_signatures, verify_log_keys,
            verify_precomp_window, verify_qtable_size)
        self.verifier = None
        if v_on:
            from ct_mapreduce_tpu.verify.lane import (
                LogKeyRegistry,
                SignatureVerifier,
            )

            keys = (LogKeyRegistry.from_json_file(v_keys) if v_keys
                    else LogKeyRegistry())
            self.verifier = SignatureVerifier(
                aggregator, keys, batch_width=v_batch,
                window=v_window, qtable_size=v_qsize)

    def store(self, entry: DecodedEntry, log_url: str) -> None:
        if entry.issuer_der is None:
            metrics.incr_counter("ct-fetch", "noChainError")
            return
        batch: Optional[list[tuple[bytes, bytes]]] = None
        cut = 0
        with self._lock:
            self._pending.append((entry.cert_der, entry.issuer_der))
            self.entries_in += 1
            if len(self._pending) >= self.flush_size:
                batch, self._pending = self._pending, []
                cut = self._open_cut()
        if batch:
            with self._handing_over(cut):
                self._dispatch(batch)

    def store_raw_batch(self, raw: "RawBatch") -> None:
        """Accumulate an undecoded get-entries response; decoded and
        dispatched natively in flush-size chunks."""
        chunk: Optional[_RawChunk] = None
        cut = 0
        with trace.span("sink.accumulate", cat="sink", n=len(raw)) as sp:
            with self._lock:
                self._pending_raw.add_page(raw)
                self.entries_in += len(raw)
                if len(self._pending_raw) >= self.flush_size:
                    chunk = self._cut_raw()
                    cut = self._open_cut()
                    sp.set(batch=chunk.batch, pages=chunk.pages,
                           logs=len({page[0] for page in chunk.pages}))
        if chunk:
            with self._handing_over(cut):
                self._dispatch_raw(chunk)

    def _cut_raw(self) -> "_RawChunk":
        """Take what has accumulated as one chunk and number it: the
        ``batch`` every span it causes carries. Caller holds ``_lock``."""
        chunk, self._pending_raw = self._pending_raw, _RawChunk()
        self._batch_seq += 1
        chunk.batch = self._batch_seq
        return chunk

    def _open_cut(self) -> int:
        """A cut left the accumulators: its number, open until it has
        been handed over. Caller holds ``_lock``."""
        self._cut_seq += 1
        self._open_cuts.add(self._cut_seq)
        return self._cut_seq

    @contextlib.contextmanager
    def _post_mortem(self):
        """Around a dispatch or a fold: one that raises leaves the
        flight dump (trace ring + metric snapshots; no-op without a
        recorder) before the exception goes on to the caller. A store
        thread catches and reports it, so the excepthook never sees
        it. The sink's first failure only: what fails after it is as a
        rule the same fault again, and one artifact tells it."""
        try:
            yield
        except Exception as err:
            with self._lock:
                first, self._failed = not self._failed, True
            if first:
                flight.dump(f"ingest dispatch failure: {err!r}")
            raise

    @contextlib.contextmanager
    def _handing_over(self, cut: int):
        """Around the dispatch of cut ``cut``, however it ends."""
        try:
            with self._post_mortem():
                yield
        finally:
            with self._cut_handed:
                self._open_cuts.discard(cut)
                self._cut_handed.notify_all()

    def _dispatch_raw(self, chunk: "_RawChunk") -> None:
        with trace.span("ingest.decode", cat="ingest", entries=len(chunk),
                        batch=chunk.batch):
            prep = self._prepare_chunk(chunk)
        t_lock = time.monotonic()
        with trace.span("ingest.submit_locked", cat="ingest",
                        batch=chunk.batch), self._dispatch_lock:
            # Lock wait sampled apart from the storeCertificate
            # envelope: multiple store workers contend here, and the
            # wait is not submit work.
            metrics.add_sample("ct-fetch", "dispatchLockWait",
                               value=time.monotonic() - t_lock)
            with metrics.measure("ct-fetch", "storeCertificate"), \
                    trace.span("ingest.submit", cat="ingest"):
                self._submit_chunk(prep)
                self._drain_inflight(self.device_queue_depth)

    def _prepare_chunk(self, chunk: "_RawChunk") -> "_PreparedChunk":
        """Stage 1 — decode + pack + H2D submit, NO aggregator-state
        mutation beyond the (thread-safe) issuer registry: safe to run
        on any thread, concurrently with device work and drains."""
        from ct_mapreduce_tpu.native import leafpack

        pages = chunk.b64_pages()
        # Row-width bucketing, now BEFORE the decode: the decoder's
        # allocation+memset scale with the pad (measured +47% decode
        # time at 2048 vs 1024 for 2^20-entry batches), and base64
        # length exactly upper-bounds the decoded leaf_input — so a
        # batch whose every leaf_input provably fits the narrow width
        # decodes straight into narrow rows. Precert entries pack
        # their cert from extra_data (not bounded by leaf_input), so
        # any TOO_LONG status triggers one full-width redecode — rare,
        # and statuses/lengths are recomputed so semantics are
        # unchanged.
        narrow = self.PAD_LEN // 2
        pad = self.PAD_LEN
        if narrow >= 512:
            max_li_raw = chunk.max_leaf_input_len() * 3 // 4
            if max_li_raw + 64 <= narrow:
                pad = narrow
        with metrics.measure("ct-fetch", "decodeBatch"):
            dec = leafpack.decode_raw_pages(
                pages, pad, workers=self.decode_workers,
                threads=self.decode_threads,
            )
            if (pad < self.PAD_LEN
                    and bool((dec.status == leafpack.TOO_LONG).any())):
                pad = self.PAD_LEN
                dec = leafpack.decode_raw_pages(
                    pages, pad, workers=self.decode_workers,
                    threads=self.decode_threads,
                )
        # Host-feed observability: the resolved intra-chunk thread
        # count; the chunk's decode cost is ct-fetch.decodeBatch.
        if len(chunk):
            metrics.set_gauge(
                "ingest", "decode_threads",
                value=float(leafpack.resolve_threads(
                    len(chunk), self.decode_threads or self.decode_workers)))
        with trace.span("decode.pack", cat="decode"):
            return self._pack_chunk(chunk, dec)

    def _pack_chunk(self, chunk: "_RawChunk", dec) -> "_PreparedChunk":
        """The Python and numpy half of stage 1, after the decoder has
        returned: narrow view, issuer registry, status accounting, the
        optional extraction passes and the H2D enqueue."""
        from ct_mapreduce_tpu.ingest.leaf import LeafDecodeError, decode_entry
        from ct_mapreduce_tpu.native import leafpack

        narrow = self.PAD_LEN // 2
        # When the batch decoded wide but every cert fits half the
        # pad, ship the narrow view — H2D bytes halve, at the price
        # of one extra compiled step variant.
        data = dec.data
        if (narrow >= 512 and data.shape[1] > narrow
                and dec.length.max(initial=0) <= narrow):
            data = data[:, :narrow]

        n = len(chunk)
        issuer_idx = np.zeros((n,), np.int32)
        oversized: list[tuple[bytes, bytes]] = []
        # Every DecodedBatch producer computes issuer groups
        # (leafpack.decode_raw_batch native/threaded/python paths); a
        # third-party producer that omits them violates the contract.
        # Not an assert: stripped under `python -O` the failure would
        # surface as an opaque TypeError below.
        if dec.issuer_group is None:
            raise ValueError(
                "DecodedBatch producer did not compute issuer groups "
                "(issuer_group/group_issuers are required)")
        # Vectorized bookkeeping: per-GROUP registry work (a handful of
        # distinct issuers per batch), numpy for the per-entry mapping
        # — no 64K-iteration Python loop.
        gmap = np.full((len(dec.group_issuers) + 1,), -1, np.int32)
        for g, der in enumerate(dec.group_issuers):
            try:
                gmap[g] = self.aggregator.registry.get_or_assign(der)
            except Exception:
                # Malformed issuer DER costs its entries, not the
                # whole chunk (per-entry path parity).
                gmap[g] = -1
        ok = dec.status == leafpack.OK
        grp = dec.issuer_group
        mapped = gmap[grp]  # grp -1 → last slot (-1 sentinel)
        valid = ok & (mapped >= 0)
        issuer_idx[valid] = mapped[valid]
        bad_issuer = int((ok & (mapped < 0)).sum())
        no_chain = int((dec.status == leafpack.NO_CHAIN).sum())
        # Both oversize flavors take the exact per-entry lane; only
        # cert-exceeds-pad (TOO_LONG) ever warranted the full-width
        # redecode above — issuer-oversize (ISSUER_TOO_LONG) certs
        # packed fine and a wider row cannot change their status.
        too_long = np.nonzero(
            (dec.status == leafpack.TOO_LONG)
            | (dec.status == leafpack.ISSUER_TOO_LONG))[0]
        other_bad = int(
            ((dec.status != leafpack.OK)
             & (dec.status != leafpack.NO_CHAIN)
             & (dec.status != leafpack.TOO_LONG)
             & (dec.status != leafpack.ISSUER_TOO_LONG)).sum()
        )
        if bad_issuer or other_bad:
            metrics.incr_counter("ct-fetch", "parseLeafError",
                                 value=float(bad_issuer + other_bad))
        if no_chain:
            metrics.incr_counter("ct-fetch", "noChainError",
                                 value=float(no_chain))
        for i in too_long:
            # Rare oversized cert: exact per-entry lane.
            try:
                import base64

                li, ed = chunk.entry_b64(int(i))
                e = decode_entry(
                    int(i), base64.b64decode(li), base64.b64decode(ed or "")
                )
            except LeafDecodeError:
                metrics.incr_counter("ct-fetch", "parseLeafError")
                continue
            if e.issuer_der is None:
                metrics.incr_counter("ct-fetch", "noChainError")
            else:
                oversized.append((e.cert_der, e.issuer_der))

        # Signature-verification lane: one more native pass over the
        # packed rows extracts embedded-SCT tuples. Runs on the decode
        # stage; classification and dispatch happen at submit time
        # under the dispatch lock. The eligible set is
        # the decoded-OK + issuer-mapped lanes BEFORE the sidecar
        # split below — walker-fallback lanes still carry auditable
        # SCTs. (Oversized certs never reach packed rows; their rare
        # SCTs are not audited — an honest gap, counted nowhere.)
        scts = None
        verify_eligible = None
        if self.verifier is not None:
            from ct_mapreduce_tpu.native import leafpack as _lp
            from ct_mapreduce_tpu.verify import sct as _sctlib

            # RFC 6962 precert digests sign the per-lane
            # issuer_key_hash: SHA-256 of the chain issuer's SPKI,
            # computed once per issuer GROUP (a handful per batch) and
            # broadcast per lane; lanes without a mapped issuer hash
            # as all-zero and can only verify against fixture SCTs
            # signed the same way.
            ikh_groups = np.zeros((len(dec.group_issuers) + 1, 32),
                                  np.uint8)
            for g, der in enumerate(dec.group_issuers):
                ikh_groups[g] = np.frombuffer(
                    _sctlib.issuer_key_hash_of(der), np.uint8)
            lane_ikh = ikh_groups[np.where(valid, grp, -1)]
            scts = _lp.extract_scts(
                data, dec.length,
                threads=self.decode_threads or self.decode_workers,
                issuer_key_hash=lane_ikh)
            verify_eligible = valid.copy()

        # Pre-parsed lane: extract walker-exact sidecars on the host
        # (one more native pass over the just-packed rows — cache-warm)
        # and split undecidable lanes out for the device-walker replay.
        sidecar = None
        walker_fallback: list[tuple[bytes, bytes]] = []
        if self.preparsed:
            sidecar = leafpack.extract_sidecars(
                data, dec.length,
                threads=self.decode_threads or self.decode_workers)
            if sidecar is not None:
                pre_ok = sidecar.ok.astype(bool)
                for i in np.nonzero(valid & ~pre_ok)[0]:
                    # Rare walker-undecidable lane: replay through the
                    # device-walker path (aggregator.ingest), exactly
                    # what the default lane would do with it.
                    walker_fallback.append((
                        data[i, : dec.length[i]].tobytes(),
                        dec.group_issuers[int(dec.issuer_group[i])],
                    ))
                valid = valid & pre_ok

        # Start the H2D transfer of the big byte rows BEFORE taking the
        # dispatch lock: device_put enqueues asynchronously, so the
        # transfer of batch N+1 overlaps the device step of batch N.
        # Small arrays stay host-side — the aggregator reads them for
        # bookkeeping. Tail chunks (not a multiple of the compiled
        # batch shape) take the NumPy path: their padding copy happens
        # host-side in the aggregator. The pre-parsed lane never
        # transfers rows at all (its device inputs are the compact
        # per-lane fields).
        data_host = data
        if (sidecar is None and valid.any()
                and data.shape[0] % self.aggregator.batch_size == 0):
            # Where the rows go is the aggregator's to say: one chip's
            # default device, or each row block straight to its chip of
            # the mesh (the only time the rows cross to the device).
            # Timing note: device_put ENQUEUES asynchronously, so this
            # sample is submit cost; the transfer itself overlaps the
            # previous step and any residual lands in completeBatch.
            with metrics.measure("ct-fetch", "h2dSubmit"):
                data = self.aggregator.put_rows(data)
        return _PreparedChunk(
            data=data, host_data=data_host, length=dec.length,
            issuer_idx=issuer_idx, valid=valid, dec=dec,
            oversized=oversized, sidecar=sidecar,
            walker_fallback=walker_fallback,
            scts=scts, verify_eligible=verify_eligible,
            batch=chunk.batch,
        )

    def _submit_verify(self, prep: "_PreparedChunk") -> None:
        """Route one prepared chunk's SCT lanes into the verify lane.
        Caller holds ``_dispatch_lock`` (the verifier shares the one
        device stream with the dedup dispatch)."""
        if self.verifier is None or prep.scts is None:
            return
        self.verifier.submit_chunk(
            prep.scts, prep.issuer_idx, prep.verify_eligible,
            prep.host_data, prep.length,
        )

    def _submit_chunk(self, prep: "_PreparedChunk") -> None:
        """Stage 2 — dispatch the device step(s) for a prepared chunk.
        Caller MUST hold ``_dispatch_lock`` (one device stream; the
        donated table state serializes submissions). The pending goes
        in flight (its ``complete()`` is stage 3); the rare exact
        lanes (sidecar-undecidable, oversized) come back complete and
        only need their PEMs folded."""
        self._submit_verify(prep)
        if prep.valid.any():
            if prep.sidecar is not None:
                pending = self.aggregator.ingest_preparsed_submit(
                    prep.sidecar, prep.issuer_idx, prep.valid,
                    prep.host_data, prep.length,
                )
            else:
                pending = self.aggregator.ingest_packed_submit(
                    prep.data, prep.length, prep.issuer_idx, prep.valid,
                    host_data=prep.host_data,
                )
            pending.batch = prep.batch
            dec = prep.dec
            self._inflight.append((
                pending,
                lambda pos, _d=dec: _d.data[pos, : _d.length[pos]].tobytes(),
            ))
        for exact in (prep.walker_fallback, prep.oversized):
            if exact:
                self._store_pems(self.aggregator.ingest(exact),
                                 lambda pos, _o=exact: _o[pos][0])
        metrics.incr_counter(
            "ct-fetch", "insertCertificate",
            value=float(int(prep.valid.sum()) + len(prep.oversized)
                        + len(prep.walker_fallback)),
        )

    def _complete_item(self, pending, der_of) -> None:
        """Stage 3 — block on one batch's device work and fold it.

        The completeBatch sample is where the pipeline's device wait
        really lives: device execution + D2H readback + the exact
        host-lane work for flagged lanes — the counterpart of the
        (async-enqueue) storeCertificate/h2dSubmit samples."""
        with metrics.measure("ct-fetch", "completeBatch"), \
                trace.span("device.readback", cat="device",
                           batch=pending.batch):
            res = pending.complete()
        # With the completeBatch sample just emitted, the contract a
        # reader's window rests on (docs/METRICS.md): one sample per
        # folded batch, in fold order, then the lanes it folded.
        metrics.incr_counter("ct-fetch", "foldedEntries",
                             value=float(len(res.was_unknown)))
        self._store_pems(res, der_of)

    def _drain_inflight(self, keep: int) -> None:
        """Complete submitted device work until at most ``keep`` batches
        remain in flight. Caller holds ``_dispatch_lock``."""
        while len(self._inflight) > keep:
            pending, der_of = self._inflight.popleft()
            self._complete_item(pending, der_of)

    def flush(self) -> None:
        raw = None
        with self._lock:
            batch, self._pending = self._pending, []
            if self._pending_raw:
                raw = self._cut_raw()
            mine = self._open_cut()
        with self._handing_over(mine):
            if batch:
                self._dispatch(batch)
            if raw:
                self._dispatch_raw(raw)
        # A cut that a store thread (or another flush) took before this
        # one looked and has not handed over yet is in neither place
        # this flush reads: its entries count as stored for their logs'
        # cursors, so the barrier waits for it. Later cuts are not its.
        with self._cut_handed:
            self._cut_handed.wait_for(
                lambda: not any(c < mine for c in self._open_cuts))
        # Same storeCertificate envelope as the dispatch path, so every
        # completeBatch sample is NESTED inside a storeCertificate
        # sample — a budget breakdown subtracts one from the other and
        # flush-path completes must not skew it.
        t_lock = time.monotonic()
        with self._post_mortem(), self._dispatch_lock:
            metrics.add_sample("ct-fetch", "dispatchLockWait",
                               value=time.monotonic() - t_lock)
            with metrics.measure("ct-fetch", "storeCertificate"):
                self._drain_inflight(0)
                if self.verifier is not None:
                    # Barrier for the verify lane too: the partial
                    # device batch dispatches and every verdict folds.
                    self.verifier.drain()

    def close(self) -> None:
        """The last flush. The sink owns no thread, so it stays usable."""
        self.flush()

    def checkpointed_save(self, save_fn) -> None:
        """Flush pending entries, then run ``save_fn`` while holding the
        dispatch lock — so snapshots never observe a mid-step (donated)
        table. Used as the engine's pre-cursor-save hook: aggregate
        state must be durable BEFORE the log cursor advances past the
        entries it contains (the reference gets this for free because
        every Redis write is durable per entry)."""
        self.flush()
        with self._dispatch_lock:
            save_fn()

    def _dispatch(self, batch: list[tuple[bytes, bytes]]) -> None:
        # The aggregator's table state is donated between steps; concurrent
        # ingest calls would race on a deleted buffer.
        with self._dispatch_lock, metrics.measure("ct-fetch", "storeCertificate"):
            result = self.aggregator.ingest(batch)
            self._store_pems(result, lambda pos: batch[pos][0])
        metrics.incr_counter(
            "ct-fetch", "insertCertificate", value=float(len(batch))
        )

    def _store_pems(self, result, der_of) -> None:
        """Durable PEM tree + dirty markers (parity with
        filesystemdatabase.go:189-208). No-op without a backend.

        PEMs are written for first-seen certs only, but every
        non-filtered entry re-marks its expiry day dirty — the
        reference marks per Store call, known duplicates included
        (filesystemdatabase.go:141-144,204-208); here that collapses
        to once per day per dispatch."""
        if self.backend is None:
            return
        from ct_mapreduce_tpu.core.der import der_to_pem
        from ct_mapreduce_tpu.core.types import ExpDate, Serial

        reg = self.aggregator.registry
        with self._pem_lock:  # store workers + per-entry path may race
            dirty_days: set[str] = set()
            for pos, sb in enumerate(result.serials):
                if sb is None or result.filtered[pos]:
                    continue
                exp = ExpDate.from_unix_hour(int(result.exp_hours[pos]))
                dirty_days.add(exp.date.strftime("%Y-%m-%d"))
                if not result.was_unknown[pos]:
                    continue
                issuer = reg.issuer_at(int(result.issuer_idx[pos]))
                pair = (exp.id(), issuer.id())
                if pair not in self._allocated:
                    self.backend.allocate_exp_date_and_issuer(exp, issuer)
                    self._allocated.add(pair)
                self.backend.store_certificate_pem(
                    Serial(sb), exp, issuer, der_to_pem(der_of(pos))
                )
            for day in dirty_days:
                self.backend.mark_dirty(day)


@dataclass
class _PreparedChunk:
    """Output of the ingest pipeline's decode stage: one raw chunk
    decoded, packed, issuer-mapped, and (when full-batch-shaped) with
    its H2D transfer already submitted — everything the device submit
    stage needs, computed without any aggregator-state mutation."""

    data: object  # uint8[n, pad] rows — device array (H2D enqueued) or np
    host_data: np.ndarray  # host-resident copy for host-lane slices
    length: np.ndarray  # int32[n]
    issuer_idx: np.ndarray  # int32[n] registry indices
    valid: np.ndarray  # bool[n]
    dec: object  # the DecodedBatch (host rows for PEM der_of slicing)
    oversized: list  # [(cert_der, issuer_der)] exact-lane entries
    sidecar: object = None  # leafpack.Sidecar — pre-parsed lane active
    walker_fallback: list = field(default_factory=list)  # sidecar-
    # undecidable lanes, replayed through the device-walker path
    scts: object = None  # verify.sct.SctBatch — verify lane active
    verify_eligible: object = None  # bool[n] — decoded-OK lanes as of
    # extraction time (pre sidecar-split)
    batch: int = 0  # the raw chunk's number (0: not from a raw cut)


@dataclass
class _QueueItem:
    entry: DecodedEntry
    log_url: str


class _RawChunk:
    """The get-entries responses accumulating toward one device batch,
    kept as they came (a response is never taken apart into entries);
    the sink's cut gives the chunk its number, the identity its spans
    share. ``len()`` is the entry count."""

    batch = 0

    def __init__(self) -> None:
        self.raws: list[RawBatch] = []
        self.entries = 0
        # [log, first index, last index], the log as fetch.page names it
        self.pages: list[list] = []

    def __len__(self) -> int:
        return self.entries

    def add_page(self, raw: "RawBatch") -> None:
        self.raws.append(raw)
        self.entries += len(raw)
        log = short_url(raw.log_url)
        first, last = raw.start_index, raw.start_index + len(raw) - 1
        if (self.pages and self.pages[-1][0] == log
                and self.pages[-1][2] + 1 == first):
            self.pages[-1][2] = last  # contiguous: one range
        else:
            self.pages.append([log, first, last])

    def b64_pages(self) -> list:
        """What ``leafpack.decode_raw_pages`` takes, a page a response."""
        return [raw.page for raw in self.raws]

    def max_leaf_input_len(self) -> int:
        """The longest base64 ``leaf_input`` of the chunk: a ``max`` over
        the numbers the pages carry from their scan, no array read."""
        return max((raw.page.max_leaf_input_len() for raw in self.raws),
                   default=0)

    def entry_b64(self, i: int) -> tuple:
        """Entry ``i``'s base64 pair, for the few lanes that go back
        through the per-entry decoder."""
        for raw in self.raws:
            if i < len(raw):
                return raw.page.leaf_input(i), raw.page.extra_data(i)
            i -= len(raw)
        raise IndexError(i)


class RawBatch:
    """One get-entries response, undecoded — the raw-batch fast path
    hands whole responses to the sink, which decodes them natively
    (ct_mapreduce_tpu.native.leafpack) with no per-entry Python.

    ``page`` is the response: two lists of base64 strings
    (``leafpack.StrPage``: tests and the audit driver build it so) or its bytes with where each value lies in them
    (``leafpack.EntryPage``: what the downloader enqueues). Reading
    ``leaf_inputs`` or ``extra_datas`` of the second kind cuts the
    strings out of the body and makes it the first kind from then on,
    so code that edits the lists finds its edit decoded."""

    def __init__(self, leaf_inputs: Optional[list] = None,
                 extra_datas: Optional[list] = None, start_index: int = 0,
                 log_url: str = "", page: Optional[EntryPage] = None):
        self.page = (page if page is not None
                     else StrPage(leaf_inputs, extra_datas))
        self.start_index = start_index
        self.log_url = log_url

    def __len__(self) -> int:
        return len(self.page)

    def _lists(self) -> StrPage:
        if isinstance(self.page, EntryPage):
            self.page = StrPage(*(
                [b.decode("utf-8", "surrogatepass") for b in col]
                for col in self.page.items()))
        return self.page

    @property
    def leaf_inputs(self) -> list:
        return self._lists().leaf_inputs

    @property
    def extra_datas(self) -> list:
        return self._lists().extra_datas


class LogWorker:
    """Download worker for one log (ct-fetch.go:248-488).

    Resolves the resume window on construction: start = saved
    ``MaxEntry`` unless ``offset`` overrides; end = STH tree size - 1,
    clamped by ``limit`` (ct-fetch.go:288-305).
    """

    def __init__(
        self,
        client: CTLogClient,
        database,
        offset: int = 0,
        limit: int = 0,
        pre_save=None,
        state_suffix: str = "",
        exit_save=None,
    ):
        self.client = client
        self.database = database
        self.pre_save = pre_save  # runs before each durable cursor write
        # Takes the worker at its clean exit in place of ``save_state``:
        # the engine's one checkpoint a round (LogSyncEngine._exit_save).
        self.exit_save = exit_save
        # Fleet stripe mode (ingest/fleet.py::partition_range): workers
        # share one log but own disjoint [offset, offset+limit) index
        # ranges, so each stripe keeps its OWN durable cursor under
        # `<short_url><state_suffix>` — a shared cursor would clobber
        # across workers — and resume takes max(stripe start, saved
        # cursor) with the stripe END fixed, so a warm restart replays
        # only the post-checkpoint tail of its own stripe.
        self.state_suffix = state_suffix
        self.sth = client.get_sth()
        self.log_state: CertificateLog = database.get_log_state(
            client.short_url + state_suffix)
        tree_end = self.sth.tree_size - 1
        if state_suffix:
            self.start_pos = max(offset, self.log_state.max_entry)
            self.end_pos = (min(offset + limit - 1, tree_end)
                            if limit > 0 else tree_end)
        else:
            if offset > 0:
                self.start_pos = offset
            else:
                self.start_pos = self.log_state.max_entry
            if limit > 0:
                self.end_pos = min(self.start_pos + limit - 1, tree_end)
            else:
                self.end_pos = tree_end
        self.position = self.start_pos
        self.last_entry_time: Optional[datetime] = None
        self._publish_lag()
        # External checkpoint trigger (fleet epoch ticks): the download
        # loop saves at the next batch boundary when set — same thread
        # as the periodic ticker saves, so no new concurrency.
        self._save_signal = threading.Event()

    def _publish_lag(self) -> None:
        """Ingest-lag gauge (round 23): entries between the cursor and
        the STH tree head for this worker's range — the raw signal the
        SLO layer (telemetry/fleetobs.py) compares against
        ``sloMaxIngestLag``. Keyed per log so multi-log runs expose the
        worst log, not a blended number."""
        lag = max(0, self.end_pos + 1 - self.position)
        metrics.set_gauge("ingest", "lag_entries", self.client.short_url,
                          value=float(lag))

    def request_save(self) -> None:
        """Ask the download loop to checkpoint (cursor + pre_save
        aggregate snapshot) at its next batch boundary."""
        self._save_signal.set()

    @property
    def moved(self) -> bool:
        """Whether the cursor stands anywhere but where it is durable:
        a log that gave nothing since its last save has nothing for a
        checkpoint to cover."""
        return self.position != self.log_state.max_entry

    def save_state(self, reason: str = "exit", covered: bool = False) -> None:
        """Persist the cursor (ct-fetch.go:371-392): dual-written by
        the database facade (cache + backend). ``pre_save`` (e.g. the
        aggregate snapshot) must succeed first — a cursor must never
        durably advance past entries whose aggregation isn't durable.
        ``reason`` (exit / savePeriod / fleet) names the save in the
        trace, and the checkpoint it causes. ``covered``: the caller
        has a checkpoint on disk that holds every entry up to
        ``position`` (or the cursor did not move), so ``pre_save`` is
        not run again for this log."""
        with trace.span("fetch.save_cursor", cat="fetch",
                        log=self.client.short_url, position=self.position,
                        reason=reason):
            if self.pre_save is not None and not covered:
                self.pre_save()
            self.log_state.max_entry = self.position
            if self.last_entry_time is not None:
                self.log_state.last_entry_time = self.last_entry_time
            self.log_state.last_update_time = datetime.now(timezone.utc)
            with metrics.measure("LogWorker", self.client.short_url,
                                 "saveState"):
                self.database.save_log_state(self.log_state)

    def _periodic_save(self, next_save: float, save_period_s: float) -> float:
        """Save if the fleet asked or the period is up; returns when
        the next periodic save is due."""
        asked = self._save_signal.is_set()
        if not asked and time.monotonic() < next_save:
            return next_save
        self._save_signal.clear()
        self.save_state("fleet" if asked else "savePeriod")
        return time.monotonic() + save_period_s

    def run(
        self,
        out: "_EntryChannel",
        stop: threading.Event,
        save_period_s: float = 900.0,
        progress=None,
        raw_batches: bool = False,
    ) -> int:
        """Stream ``[start_pos, end_pos]`` into the queue; returns the
        number of entries enqueued. Checkpoints on a ticker and at exit
        (ct-fetch.go:360-368,472-473) — the exit save runs on error
        paths too, like the reference's deferred save (ct-fetch.go:367):
        a transport error mid-range must not discard up to a full save
        period of cursor progress (re-fetch is dedup-safe, but it is
        lost work). With ``raw_batches``, whole get-entries responses
        are enqueued undecoded for the sink's native batch decoder."""
        try:
            enqueued = self._run_loop(
                out, stop, save_period_s, progress, raw_batches
            )
        except BaseException:
            # Best-effort save on the error path: a failing save must
            # not replace the root-cause download error (the engine
            # records what propagates to it).
            try:
                self.save_state()
            except Exception:
                metrics.incr_counter(
                    "LogWorker", self.client.short_url, "saveStateError"
                )
            raise
        if self.exit_save is not None:
            self.exit_save(self)
        else:
            self.save_state()
        return enqueued

    def _run_loop(
        self, out, stop, save_period_s, progress, raw_batches
    ) -> int:
        enqueued = 0
        next_save = time.monotonic() + save_period_s
        index = self.position
        while index <= self.end_pos and not stop.is_set():
            if raw_batches:
                with trace.span("fetch.page", cat="fetch",
                                log=self.client.short_url,
                                start=index) as page:
                    got = self._fetch_raw_page(index, out, stop, progress)
                    page.set(n=got)
                if got <= 0:
                    break
                enqueued += got
                index = self.position
                next_save = self._periodic_save(next_save, save_period_s)
                continue
            batch = self.client.get_raw_entries(
                index, min(index + BATCH_SIZE - 1, self.end_pos)
            )
            if not batch:
                break
            for raw in batch:
                try:
                    with metrics.measure(
                        "LogWorker", self.client.short_url, "parseLeaf"
                    ):
                        entry = decode_json_entry(
                            raw.index,
                            {"leaf_input": raw.leaf_input,
                             "extra_data": raw.extra_data},
                        )
                except LeafDecodeError:
                    metrics.incr_counter(
                        "LogWorker", self.client.short_url, "parseLeafError"
                    )
                    # Tolerated skip IS durable: the cursor moves past the
                    # bad entry so restarts don't re-fetch it forever.
                    self.position = raw.index + 1
                    continue
                finally:
                    index = raw.index + 1
                self.last_entry_time = datetime.fromtimestamp(
                    entry.timestamp_ms / 1000.0, tz=timezone.utc
                )
                # select{signal | save | submit} (ct-fetch.go:466-480)
                submitted = False
                while not stop.is_set():
                    try:
                        with metrics.measure(
                            "LogWorker", self.client.short_url, "submitToChannel"
                        ):
                            out.put(_QueueItem(entry, self.client.log_url),
                                    timeout=0.25)
                        enqueued += 1
                        submitted = True
                        break
                    except queue.Full:
                        continue
                if not submitted:
                    # Stopped while the queue was full: do NOT advance the
                    # cursor past an entry that never reached a worker —
                    # resume must re-fetch it.
                    break
                self.position = raw.index + 1
                self._publish_lag()
                if progress is not None:
                    progress(self.client.short_url, self.position, self.end_pos)
                next_save = self._periodic_save(next_save, save_period_s)
                if stop.is_set():
                    break
        return enqueued

    def _fetch_raw_page(self, index: int, out, stop, progress) -> int:
        """One get-entries response from ``index`` into the queue,
        undecoded; returns the entries enqueued (0: the log gave none,
        or the run stopped while the queue was full — the cursor then
        stays put, the page never reached a worker)."""
        page = self.client.get_entry_page(
            index, min(index + BATCH_SIZE - 1, self.end_pos)
        )
        if not len(page):
            return 0
        item = RawBatch(start_index=index, log_url=self.client.log_url,
                        page=page)
        # Back-pressure: the time the downloader stands still because
        # the store side has not taken what it already fetched.
        submitted = False
        with trace.span("fetch.enqueue", cat="fetch", depth=out.depth(),
                        entries=out.qsize()), \
                metrics.measure("LogWorker", self.client.short_url,
                                "submitToChannel"):
            while not stop.is_set():
                try:
                    out.put(item, timeout=0.25)
                    submitted = True
                    break
                except queue.Full:
                    continue
        if not submitted:
            return 0
        self.position = index + len(page)
        # Last DECODABLE timestamp — a garbage final entry must
        # not lose the good entries' timestamps (per-entry-path
        # parity: it updates per decoded entry).
        for i in reversed(range(len(page))):
            ts = decode_leaf_timestamp(page.leaf_input(i))
            if ts is not None:
                self.last_entry_time = datetime.fromtimestamp(
                    ts / 1000.0, tz=timezone.utc
                )
                break
        self._publish_lag()
        if progress is not None:
            progress(self.client.short_url, self.position, self.end_pos)
        return len(page)


def _entries_of(item) -> int:
    """What an item of the channel weighs: a response its entries, an
    entry one; so do an empty response and the ``None`` a store thread
    stops on, because ``get`` reads a channel of weight 0 as empty."""
    return max(1, len(item)) if isinstance(item, RawBatch) else 1


class _EntryChannel(queue.Queue):
    """The shared entry channel, bounded by the ENTRIES it holds and not
    by its items: a get-entries response weighs what it carries (a log
    caps a response anywhere from 32 to 1,024 entries), a decoded entry
    one. ``put`` admits an item while the entries already inside are
    under ``capacity``, so a response larger than the room left, or than
    the whole bound, still goes in and nothing deadlocks on an odd page
    size; the channel then holds less than ``capacity`` plus one
    response. Blocking, timeouts, ``task_done``/``join`` are
    ``queue.Queue``'s own (they count items); ``qsize()`` is entries,
    ``depth()`` items."""

    def __init__(self, capacity: int):
        self.high_water = 0  # most entries held at once
        super().__init__(maxsize=max(1, int(capacity)))

    @property
    def capacity(self) -> int:
        return self.maxsize

    def _init(self, maxsize: int) -> None:
        self.queue: deque = deque()
        self._entries = 0

    def _qsize(self) -> int:
        return self._entries

    def _put(self, item) -> None:
        self.queue.append(item)
        self._entries += _entries_of(item)
        if self._entries > self.high_water:
            self.high_water = self._entries
            metrics.set_gauge("ingest", "channel_high_water_entries",
                              value=float(self._entries))
        if self._entries < self.capacity:
            # One `get` can make room for several small responses and
            # wakes one putter: pass the baton while there is room.
            self.not_full.notify()

    def _get(self):
        item = self.queue.popleft()
        self._entries -= _entries_of(item)
        return item

    def depth(self) -> int:
        with self.mutex:
            return len(self.queue)


class _AccountingQueue:
    """Facade over the shared entry channel that bumps the engine's
    per-log outstanding watermark on each successful put (the blocking
    semantics are the channel's own)."""

    def __init__(self, inner: _EntryChannel, on_put):
        self._inner = inner
        self._on_put = on_put

    def put(self, item, timeout=None) -> None:
        self._inner.put(item, timeout=timeout)
        self._on_put(item)

    def qsize(self) -> int:
        return self._inner.qsize()

    def depth(self) -> int:
        return self._inner.depth()


class LogSyncEngine:
    """Queue + worker-pool runtime (ct-fetch.go:83-178).

    ``start_store_threads`` spawns the consumers; ``sync_log`` spawns
    one downloader thread per URL; ``stop`` + ``join`` replicate the
    WaitGroup shutdown ordering of main() (ct-fetch.go:610-620).

    All logs share one channel, bounded in entries
    (:class:`_EntryChannel`). Per-entry mode: ``queue_capacity``, the
    reference's 16,384 (ct-fetch.go:132), in front of a consumer that
    takes an entry at a time. ``raw_batches``: the sink takes responses
    at memcpy speed until one cuts a batch and then leaves the channel
    alone for as long as that batch's decode, submit and fold take, so
    the channel holds one batch of the sink it feeds (``flush_size``
    entries, which is part of what a raw-batch sink is) and the
    downloaders fetch through the pause, however short a batch makes
    it. The width is derived, not set: host memory is one
    batch of response bytes a process, beside the batch the sink
    accumulates. A cursor save still waits for every entry its log
    enqueued (``_pre_cursor_save``): the width changes how much can be
    outstanding, never whether the cursor may pass it.
    """

    def __init__(
        self,
        sink: EntrySink,
        database,
        num_threads: int = 1,
        queue_capacity: int = ENTRY_QUEUE_CAPACITY,
        offset: int = 0,
        limit: int = 0,
        save_period_s: float = 900.0,
        checkpoint_hook=None,
        raw_batches: bool = False,
    ):
        self.sink = sink
        self.database = database
        # Runs before each durable cursor write (after the queue drains):
        # in TPU mode this snapshots the device aggregates so the cursor
        # never outruns durable aggregate state.
        self.checkpoint_hook = checkpoint_hook
        self.num_threads = num_threads
        self.offset = offset
        self.limit = limit
        self.save_period_s = save_period_s
        self.raw_batches = raw_batches
        if raw_batches:
            # Items are whole get-entries responses and the consumer
            # pauses once a batch: hold one batch of the sink's.
            queue_capacity = int(sink.flush_size)
        self.entry_queue = _EntryChannel(queue_capacity)
        metrics.set_gauge("ingest", "channel_capacity_entries",
                          value=float(self.entry_queue.capacity))
        self.stop_event = threading.Event()
        self._store_threads: list[threading.Thread] = []
        self._download_threads: list[threading.Thread] = []
        self._last_update_lock = threading.Lock()
        self._last_updates: dict[str, datetime] = {}
        self._progress: dict[str, tuple[int, int]] = {}
        self.errors: list[str] = []
        # Per-log count of entries enqueued but not yet through the sink.
        # A durable cursor save for log L only needs L's own entries
        # stored — waiting on the whole shared queue (entry_queue.join())
        # would let other logs' downloaders starve the save indefinitely.
        self._outstanding: dict[str, int] = {}
        self._outstanding_cond = threading.Condition()
        # Live LogWorkers (fleet checkpoint fan-out): registered for
        # the duration of their download, so an external checkpoint
        # tick can ask each to save at its next batch boundary.
        self._active_workers: list[LogWorker] = []
        self._active_lock = threading.Lock()
        # One checkpoint a round (_exit_save): the seats of the
        # downloaders that still fetch, the workers that have reached
        # their end and wait for the checkpoint that covers them, how a
        # save that claimed one fared (did its cursor land), and
        # whether a checkpoint is being written now.
        self._round_cond = threading.Condition()
        self._fetching: set = set()
        self._round_logs = 0
        self._parked: list[LogWorker] = []
        self._covered: dict[LogWorker, bool] = {}
        self._saving = 0
        # When anything last moved (a response enqueued, stored, a
        # checkpoint finished) and the longest the round has seen
        # nothing move: what a parked log judges a standstill by.
        self._moved_at = time.monotonic()
        self._longest_pause = 0.0

    # -- health surface (ct-fetch.go:567-597) ---------------------------
    def last_updates(self) -> dict[str, datetime]:
        with self._last_update_lock:
            return dict(self._last_updates)

    def progress(self) -> dict[str, tuple[int, int]]:
        with self._last_update_lock:
            return dict(self._progress)

    def _note_progress(self, short_url: str, pos: int, end: int) -> None:
        with self._last_update_lock:
            self._last_updates[short_url] = datetime.now(timezone.utc)
            self._progress[short_url] = (pos, end)

    # -- consumers ------------------------------------------------------
    def _store_worker(self) -> None:
        while True:
            # Starvation: the store side waiting for the downloaders.
            with trace.span("sink.queue_wait", cat="sink"), \
                    metrics.measure("ct-fetch", "queueWait"):
                item = self.entry_queue.get()
            try:
                if item is None:
                    return
                try:
                    if isinstance(item, RawBatch):
                        self.sink.store_raw_batch(item)
                    else:
                        self.sink.store(item.entry, item.log_url)
                except Exception as err:
                    # A store failure must not kill the worker — the queue
                    # would back up and stop() would deadlock on join().
                    metrics.incr_counter("ct-fetch", "storeError")
                    where = (
                        f"{item.log_url}@{item.start_index}"
                        if isinstance(item, RawBatch)
                        else f"{item.log_url}@{item.entry.index}"
                    )
                    self.errors.append(f"store {where}: {err}")
            finally:
                self.entry_queue.task_done()
                if item is not None:
                    self._account_stored(item)

    def start_store_threads(self) -> None:
        for i in range(self.num_threads):
            t = threading.Thread(
                target=self._store_worker, name=f"store-{i}", daemon=True
            )
            t.start()
            self._store_threads.append(t)

    def _account_enqueued(self, item) -> None:
        n = _entries_of(item)
        with self._outstanding_cond:
            self._outstanding[item.log_url] = (
                self._outstanding.get(item.log_url, 0) + n
            )
            self._note_moved()

    def _account_stored(self, item) -> None:
        n = _entries_of(item)
        with self._outstanding_cond:
            self._outstanding[item.log_url] = (
                self._outstanding.get(item.log_url, 0) - n
            )
            self._note_moved()
            self._outstanding_cond.notify_all()

    def _note_moved(self) -> None:
        """Something moved: a response went into the channel or through
        the sink, a checkpoint ended. Caller holds ``_outstanding_cond``."""
        now = time.monotonic()
        self._longest_pause = max(self._longest_pause, now - self._moved_at)
        self._moved_at = now

    # -- one checkpoint a round -------------------------------------------
    # A cursor save costs a checkpoint of the whole table (the reference
    # pays a Redis write), so a round of N logs must not pay N. A log
    # that reaches its end while others of the round still fetch parks:
    # it neither flushes the shared accumulator nor saves. Whoever saves
    # next — the round's last downloader at its own exit, a savePeriod
    # or fleet tick of a running one, an error-path save — writes ONE
    # checkpoint and after it the cursor of every parked log whose
    # entries had all passed the sink before that checkpoint's flush.
    # The order of the guarantee is unchanged for every log: the
    # aggregate on disk covers an entry before any cursor on disk does.
    STILL_FLOOR_S = 2.0  # a standstill is at least this long ...
    STILL_PAUSES = 4.0  # ... and this many of the round's longest pause

    def _join_round(self) -> object:
        """A downloader starts: its seat among those still fetching."""
        seat = object()
        with self._round_cond:
            if not self._fetching and not self._parked:
                self._round_logs = 0
                with self._outstanding_cond:
                    self._moved_at = time.monotonic()
                    self._longest_pause = 0.0
            self._fetching.add(seat)
            self._round_logs += 1
        return seat

    def _leave_round(self, seat: object) -> None:
        """The downloader fetches no more (whoever says so first)."""
        with self._round_cond:
            self._fetching.discard(seat)
            self._round_cond.notify_all()

    def _standstill_in_s(self) -> float:
        """Seconds until the parked caller may call the running
        downloaders stood still (0: now). Nothing of theirs went into
        the channel and nothing through the sink for ``STILL_PAUSES``
        times the longest such pause the round has seen (a transport
        that blocks, a log in back-off); a checkpoint being written
        holds everything, so the clock starts after it. Caller holds
        ``_round_cond``."""
        if self._saving:
            return self.STILL_FLOOR_S
        bound = max(self.STILL_FLOOR_S,
                    self.STILL_PAUSES * self._longest_pause)
        return max(0.0, bound - (time.monotonic() - self._moved_at))

    def _exit_save(self, worker: LogWorker, seat: object) -> None:
        """A downloader's clean exit (``LogWorker.exit_save``). A log
        whose cursor did not move saves nothing. The round's last
        downloader saves for everyone parked; one that ends earlier
        parks until a checkpoint has covered it, or until the others
        stand still, when it saves as a log alone would."""
        with trace.span("round.cursor_wait", cat="round",
                        log=worker.client.short_url,
                        position=worker.position) as sp:
            moved = worker.moved
            if self.checkpoint_hook is None or not moved:
                # No checkpoint to share (a cursor save costs a cursor
                # write), or nothing new for one to cover.
                sp.set(how="alone" if moved else "unmoved")
                worker.save_state(covered=not moved)
                return
            closing: list[LogWorker] = []
            with self._round_cond:
                self._leave_round(seat)
                self._parked.append(worker)
                while worker in self._parked and self._fetching:
                    wait_s = self._standstill_in_s()
                    if wait_s <= 0.0:
                        break
                    self._round_cond.wait(wait_s)
                if worker in self._parked:
                    self._parked.remove(worker)
                    how = "gave_up"
                    if not self._fetching:
                        # The round's last: everyone parked is its to
                        # save (under this lock, so one of them closes).
                        how = "closed"
                        closing, self._parked = self._parked, []
                else:  # a save claimed this log: its outcome
                    self._round_cond.wait_for(lambda: worker in self._covered)
                    how = ("covered" if self._covered.pop(worker)
                           else "save_failed")
            sp.set(how=how)
            if how == "covered":
                return
            with trace.span("round.save", cat="round", reason="exit",
                            logs=self._round_logs) as save:
                saved = [worker, *self._pre_cursor_save(
                    worker.client.log_url, closing)]
                worker.save_state(covered=True)
                save.set(cursors=len(saved), entries=sum(
                    w.position - w.start_pos for w in saved))

    def _pre_cursor_save(self, log_url: str,
                         closing: Optional[list] = None) -> list[LogWorker]:
        """Make everything log ``log_url``'s cursor covers durable:
        wait until every entry *this log* enqueued has passed through
        the sink (a per-log watermark — the downloader is the one
        waiting, so its count only drains; other logs keep flowing),
        then run the checkpoint hook to flush + snapshot.

        The checkpoint also covers, and this returns, the parked logs
        whose cursors are written after it: ``closing`` (the round's
        last downloader took them all: nobody fetches any more, so
        their counts only drain too and are waited for) and whichever
        others have all their entries through the sink by then."""
        def drained(url: str) -> bool:
            return self._outstanding.get(url, 0) <= 0

        claimed = list(closing or ())
        ok = False
        try:
            with trace.span("ckpt.wait_outstanding", cat="ckpt"), \
                    self._outstanding_cond:
                self._outstanding_cond.wait_for(lambda: all(map(
                    drained, [log_url, *(w.client.log_url for w in claimed)])))
            if self.checkpoint_hook is None:
                return []
            with self._round_cond, self._outstanding_cond:
                claimed += [w for w in self._parked
                            if drained(w.client.log_url)]
                self._parked = [w for w in self._parked if w not in claimed]
                self._saving += 1
            try:
                self.checkpoint_hook()
            finally:
                with self._round_cond, self._outstanding_cond:
                    self._saving -= 1
                    self._note_moved()
            for w in claimed:
                w.save_state(covered=True)
            ok = True
        finally:
            if claimed:
                with self._round_cond:
                    self._covered.update(dict.fromkeys(claimed, ok))
                    self._round_cond.notify_all()
        return claimed

    # -- external checkpoint trigger (fleet epoch ticks) ----------------
    def checkpoint_now(self) -> None:
        """Checkpoint the run's durable state out of band: every live
        downloader saves (cursor + pre_save aggregate snapshot) at its
        next batch boundary; with no downloads in flight the aggregate
        snapshot hook runs directly, so idle workers still persist at
        the fleet's cadence."""
        with self._active_lock:
            workers = list(self._active_workers)
        for worker in workers:
            worker.request_save()
        if not workers and self.checkpoint_hook is not None:
            self.checkpoint_hook()

    # -- producers ------------------------------------------------------
    def sync_log(self, log_url: str, transport=None,
                 offset: Optional[int] = None, limit: Optional[int] = None,
                 state_suffix: str = "") -> threading.Thread:
        """Start one downloader. ``offset``/``limit`` override the
        engine-wide window (fleet entry-range stripes of a single log
        pass their own); ``state_suffix`` keys the stripe's durable
        cursor (see :class:`LogWorker`)."""
        eff_offset = self.offset if offset is None else offset
        eff_limit = self.limit if limit is None else limit

        seat = self._join_round()

        def run() -> None:
            worker = None
            try:
                client = CTLogClient(log_url, transport=transport)
                worker = LogWorker(
                    client, self.database,
                    offset=eff_offset, limit=eff_limit,
                    # Items carry the client's normalized URL, so the
                    # watermark key must match it.
                    pre_save=lambda: self._pre_cursor_save(client.log_url),
                    state_suffix=state_suffix,
                    exit_save=lambda w: self._exit_save(w, seat),
                )
                with self._active_lock:
                    self._active_workers.append(worker)
                self._note_progress(client.short_url, worker.position, worker.end_pos)
                worker.run(
                    _AccountingQueue(self.entry_queue, self._account_enqueued),
                    self.stop_event,
                    save_period_s=self.save_period_s,
                    progress=self._note_progress,
                    raw_batches=self.raw_batches,
                )
            except Exception as err:  # log-level failures never kill the run
                metrics.incr_counter("ct-fetch", "syncLogError")
                self.errors.append(f"{log_url}: {err}")
            finally:
                self._leave_round(seat)
                if worker is not None:
                    with self._active_lock:
                        with contextlib.suppress(ValueError):
                            self._active_workers.remove(worker)

        t = threading.Thread(target=run, name=f"sync-{log_url}", daemon=True)
        t.start()
        self._download_threads.append(t)
        return t

    # -- lifecycle ------------------------------------------------------
    def wait_for_downloads(self, timeout: Optional[float] = None) -> None:
        deadline = None if timeout is None else time.monotonic() + timeout
        for t in self._download_threads:
            remaining = None if deadline is None else max(0.0, deadline - time.monotonic())
            t.join(remaining)
        # Drop finished threads so runForever rounds don't accumulate
        # (and re-join) an ever-growing history.
        self._download_threads = [t for t in self._download_threads if t.is_alive()]

    def stop(self) -> None:
        """Drain and terminate the store workers (ct-fetch.go:167-171)."""
        self.entry_queue.join()
        for _ in self._store_threads:
            self.entry_queue.put(None)
        for t in self._store_threads:
            t.join()
        self._store_threads.clear()
        self.sink.flush()

    def signal_stop(self) -> None:
        self.stop_event.set()

    def cleanup(self) -> None:
        self.database.cleanup()


def polling_delay(mean_s: float, std_dev_pct: float) -> float:
    """runForever inter-poll sleep: normal around the mean, clamped
    positive (the reference draws from a normal distribution with the
    configured mean/stddev percentage)."""
    return max(1.0, random.gauss(mean_s, mean_s * std_dev_pct / 100.0))
