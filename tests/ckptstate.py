"""Checkpoint-plane fixtures shared by tests/test_ckpt.py and the
self-killing child it spawns: a pre-filled aggregator, fresh churn
through the fold path (so the dirty log sees it), and the canonical
digest of everything a checkpoint must restore.
"""

from __future__ import annotations


def _serials(start: int, n: int):
    """``n`` synthetic serials from counter ``start``: 8 zero bytes and
    the counter in 8 bytes, big-endian."""
    import numpy as np

    from ct_mapreduce_tpu.core import packing

    serials = np.zeros((n, packing.MAX_SERIAL_BYTES), np.uint8)
    serials[:, 8:16] = np.arange(
        start, start + n, dtype=">u8").view(np.uint8).reshape(n, 8)
    return serials


def build_aggregator(entries: int, table_bits: int):
    """A dedup table pre-filled with ``entries`` synthetic serials
    (8 zero bytes + 8-byte BE counter), via the bulk reinsert path."""
    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
    from ct_mapreduce_tpu.core import packing

    import numpy as np

    agg = TpuAggregator(capacity=1 << table_bits, batch_size=4096,
                        grow_at=0.0)
    eh = agg.base_hour + 1000
    serials = _serials(0, entries)
    slen = np.full((entries,), 16, np.int64)
    keys = packing.fingerprints_np(
        np.zeros((entries,), np.int64), np.full((entries,), eh, np.int64),
        serials, slen)
    meta = np.full((entries,), packing.pack_meta(0, eh, agg.base_hour),
                   np.uint32)
    ovf = agg._bulk_reinsert(keys, meta)
    assert not ovf, f"table too small: {ovf} overflow rows"
    agg._table_fill = entries
    agg._device_written = True
    return agg, eh


def ckpt_churn(agg, eh: int, n: int, start: int) -> None:
    """Fold ``n`` fresh synthetic serials (same counter space as
    :func:`build_aggregator`, starting at ``start``) through the
    PRE-PARSED lane — the bulk-reinsert path build_aggregator uses
    bypasses fold-time dirty logging, which is fine for the base
    corpus but would make incremental-checkpoint churn invisible."""
    res = fold_serials(agg, eh, n, start)
    assert int(res.was_unknown.sum()) == n, (
        f"churn batch not fresh: {int(res.was_unknown.sum())}/{n} "
        "unknown (counter overlap with the base corpus?)")


def fold_serials(agg, eh: int, n: int, start: int):
    """Fold synthetic serials ``start .. start + n`` through the
    pre-parsed lane, fresh or not; the fold's result (``was_unknown``
    a lane)."""
    import numpy as np

    from ct_mapreduce_tpu.core import packing
    from ct_mapreduce_tpu.native.leafpack import Sidecar

    s = packing.MAX_SERIAL_BYTES
    serials = _serials(start, n)
    zeros = np.zeros((n,), np.int32)
    # The fold path (unlike the bulk pre-fill) enforces the expiry
    # filter against the real clock: keep churn certs in the future
    # while staying inside the meta hour span of the base.
    nah = max(int(eh), agg._now_hour() + 1000)
    assert nah - agg.base_hour < packing.META_HOUR_SPAN, (
        "churn expiry hour outside the fixture's meta span")
    sc = Sidecar(
        ok=np.ones((n,), np.uint8),
        serial_off=zeros, serial_len=np.full((n,), 16, np.int32),
        not_after_hour=np.full((n,), nah, np.int32),
        is_ca=np.zeros((n,), np.uint8),
        has_crldp=np.zeros((n,), np.uint8),
        cn_off=zeros, cn_len=zeros, issuer_off=zeros, issuer_len=zeros,
        spki_off=zeros, spki_len=zeros, crldp_off=zeros,
        crldp_len=zeros,
    )
    return agg.ingest_preparsed(
        sc, np.zeros((n,), np.int32), np.ones((n,), bool),
        serials, np.full((n,), s, np.int32))


def ckpt_state_digest(agg) -> str:
    """Canonical SHA-256 over the complete restorable aggregate state
    (sorted table rows, count, registry, counters, host/capture sets,
    content tokens) — the restore-parity oracle: a CTMRCK02 base +
    chain restore must digest identically to a ck01 full-save
    restore of the same state."""
    import hashlib

    import numpy as np

    keys, meta = agg._drain_table()
    rows = np.concatenate(
        [keys.astype(np.uint32),
         meta.astype(np.uint32).reshape(-1, 1)], axis=1)
    order = np.lexsort(rows.T[::-1])
    h = hashlib.sha256()
    h.update(rows[order].tobytes())
    h.update(str(int(agg._table_fill)).encode())
    h.update(agg.registry.to_json().encode())
    h.update(np.trim_zeros(agg.issuer_totals, "b").tobytes())
    h.update(np.trim_zeros(agg.verify_verified, "b").tobytes())
    h.update(np.trim_zeros(agg.verify_failed, "b").tobytes())
    for (i, e), ss in sorted(agg.host_serials.items()):
        h.update(f"h{i},{e};".encode())
        for sb in sorted(ss):
            h.update(sb)
    for i, urls in sorted(agg.crl_sets.items()):
        h.update(f"c{i};".encode())
        for u in sorted(urls):
            h.update(u.encode())
    for i, dns in sorted(agg.dn_sets.items()):
        h.update(f"d{i};".encode())
        for dn in sorted(dns):
            h.update(dn.encode())
    tokens = agg.capture_content_hashes()
    if tokens is not None:
        for (i, e), v in sorted(tokens.items()):
            h.update(f"t{i},{e},{v:032x};".encode())
    return h.hexdigest()
