"""Vectorized X.509/DER field extraction on device.

Replaces the reference's per-entry CPU ``x509.ParseCertificate``
(/root/reference/cmd/ct-fetch/ct-fetch.go:198-226) for the fields the
map stage actually consumes:

- serial content offset/length (raw bytes incl. leading zeros,
  /root/reference/storage/types.go:165-178),
- notAfter as epoch-hours (the ExpDate bucket,
  /root/reference/storage/types.go:339-346),
- BasicConstraints CA flag and CRL-distribution-points presence
  (filter + metadata triggers, /root/reference/cmd/ct-fetch/ct-fetch.go:47-50,
  /root/reference/storage/issuermetadata.go:92-138),
- the issuer DN's CommonName as Go's pkix.Name fills it, or "cannot
  say" (the CN-prefix filter,
  /root/reference/cmd/ct-fetch/ct-fetch.go:56-62; _scan_issuer_cn),
- SPKI TLV offset/length (issuer identity when a lane's cert is used
  as an issuer).

Because DER fixes the field order of TBSCertificate, the walk is a
straight-line program of vectorized header reads — identical control
flow for every lane, per-lane data only in the (tag, length, position)
registers. The two variable-count regions (issuer RDNs, extensions) are
early-exiting ``while_loop``s with active-lane masks. Any structural
surprise (unsupported long-form length, window overrun, loop budget
exhausted) clears the lane's ``ok`` bit; those lanes take the host
reference lane (:mod:`ct_mapreduce_tpu.core.der`), matching the
reference's tolerate-and-skip contract
(/root/reference/cmd/ct-fetch/ct-fetch.go:206-225).

Everything is shape-static and jit/pjit-friendly; the batch axis is the
sharding axis.

Access-path design (round-3 rework): TPU gathers are the enemy — a
single per-lane ``take_along_axis`` over the [B, L] byte buffer costs
~1 ms at B=16K, and the walker needs hundreds of byte reads, which is
where the original 170 ms/batch went. This version performs **zero
gathers**: rows are packed once into big-endian uint32 words (native
uint32 — no floating point), each walk step extracts a small byte WINDOW
at its per-lane position via one-hot × shifted-slice multiply-reduce
(pure elementwise + reduction, which XLA fuses into row passes; at
production row widths the one-hot is TWO-LEVEL — select two adjacent
_BLOCK_WORDS-word blocks in one pass over the row, then window within
the superblock — cutting per-window reduce work ~nw/_BLOCK_WORDS×), and
all byte reads inside a step are one-hot selects over that ≤68-byte
window (17 words — exactly the _PAD_WORDS+1 and _BLOCK_WORDS+1
ceiling). The fixed walk merges adjacent headers into 5 shared
windows; the variable-count scans (issuer RDNs, extensions) run as
superblock loops — fetch each lane 512 bytes in one row pass, walk
TLV elements inside it at VPU speed, refetch on crossing — so a
batch pays ~one row pass per ~468 bytes of scanned region instead of
one per TLV element.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ct_mapreduce_tpu.core.der import RAW_STRING_TAGS

MAX_RDNS = 12  # RDN components scanned in the issuer Name
MAX_EXTS = 24  # extensions scanned in the TBS

_PAD_WORDS = 16  # slack words so shifted slices cover every window
# (every _window call asserts n_words <= _PAD_WORDS + 1; the binding
# consumer is window 1's 17 words = 68 bytes, which must reach the
# sigAlg HEADER past a maximum-width serial: 5+5+5+2+46+5 = 68
# exactly. 17 words also sits exactly at the _BLOCK_WORDS + 1
# two-level ceiling — there is NO slack left at this size.)

_BLOCK_WORDS = 16  # two-level window: block granularity (see _window)

_SUP_BLOCKS = 8  # superblock loops: blocks fetched per scan round
# (512 bytes — covers a typical whole extension list in ONE row pass)

# Largest content span `window_bytes_rows` can serve: its window needs
# (6 + n)//4 + 1 words, bounded by min(_PAD_WORDS, _BLOCK_WORDS) + 1.
MAX_FIXED_WINDOW_BYTES = min(_PAD_WORDS, _BLOCK_WORDS) * 4 + 3 - 6  # 61


class ParsedCerts(NamedTuple):
    """Per-lane extraction results (int32 unless noted)."""

    ok: jax.Array  # bool — False ⇒ use the host reference lane
    serial_off: jax.Array
    serial_len: jax.Array
    not_after_hour: jax.Array  # hours since Unix epoch, floor-truncated
    is_ca: jax.Array  # bool
    has_crldp: jax.Array  # bool
    issuer_cn_off: jax.Array
    issuer_cn_len: jax.Array  # 0 ⇒ no CN present; -1 ⇒ the scan cannot
    # say what Go's pkix.Name holds (see _scan_issuer_cn): host lane
    issuer_off: jax.Array  # full issuer Name TLV (host DN-cache key)
    issuer_len: jax.Array
    spki_off: jax.Array  # offset of the full SPKI TLV
    spki_len: jax.Array  # header+content length
    crldp_off: jax.Array  # CRLDP extnValue content (host CRL-cache key)
    crldp_len: jax.Array  # 0 ⇒ extension absent


class _Rows(NamedTuple):
    """Word-packed rows: big-endian uint32 words, padded for slices.

    Width is max(NW + _PAD_WORDS, ceil(NW/_BLOCK_WORDS)*_BLOCK_WORDS)
    — enough for the flat path's shifted slices AND the two-level
    path's block reshape. Build via :func:`pack_rows`, not by hand.
    """

    words: jax.Array  # uint32[B, >= NW + _PAD_WORDS] (see docstring)
    n_words: int  # NW = ceil(L / 4)


def _pack_rows(data: jax.Array) -> _Rows:
    """uint8[B, L] → :class:`_Rows` (one elementwise pass, no gathers)."""
    b, l = data.shape
    if l % 4:
        data = jnp.pad(data, ((0, 0), (0, 4 - l % 4)))
    w = (
        (data[:, 0::4].astype(jnp.uint32) << 24)
        | (data[:, 1::4].astype(jnp.uint32) << 16)
        | (data[:, 2::4].astype(jnp.uint32) << 8)
        | data[:, 3::4].astype(jnp.uint32)
    )
    nw = w.shape[1]
    # Pad so BOTH window paths are in-bounds: the flat path's shifted
    # slices need nw + _PAD_WORDS; the two-level path reshapes the
    # first ceil(nw/_BLOCK_WORDS)*_BLOCK_WORDS columns into blocks.
    blocks = -(-nw // _BLOCK_WORDS) * _BLOCK_WORDS
    return _Rows(
        jnp.pad(w, ((0, 0), (0, max(nw + _PAD_WORDS, blocks) - nw))), nw
    )


# Public names for the shared-rows interface consumed by the fused
# step (pipeline.local_lanes): pack once, share across parse / serial
# extraction / CN window.
Rows = _Rows


def pack_rows(data: jax.Array) -> _Rows:
    """Public wrapper: word-pack a uint8[B, L] batch once for the
    ``*_rows`` entry points."""
    return _pack_rows(data.astype(jnp.uint8))


def _window(rows: _Rows, p: jax.Array, n_words: int):
    """Byte window anchored at per-lane position ``p``.

    Returns ``(win int32[B, n_words*4], a int32[B])`` where window byte
    ``a + d`` is row byte ``p + d`` (``a = p & 3`` is the alignment).
    No gather anywhere: short rows use one one-hot over the word axis
    plus ``n_words`` shifted-slice multiply-reduces; production-width
    rows (nw >= 4 * _BLOCK_WORDS) take the two-level block select
    below (same result, ~nw/_BLOCK_WORDS times less reduce work).

    Caveat: positions past the packed buffer CLAMP to the final word
    (window bytes then repeat trailing row bytes, not zeros) — every
    caller masks lanes whose positions failed the `limit` checks, and
    new callers must do the same.
    """
    nw = rows.n_words
    if n_words > _PAD_WORDS + 1:
        raise ValueError(
            f"window of {n_words} words exceeds _PAD_WORDS + 1 "
            f"({_PAD_WORDS + 1}); raise _PAD_WORDS"
        )
    if n_words > _BLOCK_WORDS + 1:
        # Two-level constraint: the superblock read is sup[loc + k]
        # with loc < _BLOCK_WORDS and k < n_words, which must stay
        # inside the 2*_BLOCK_WORDS superblock.
        raise ValueError(
            f"window of {n_words} words exceeds _BLOCK_WORDS + 1 "
            f"({_BLOCK_WORDS + 1}); raise _BLOCK_WORDS too"
        )
    base = jnp.clip(p, 0, (nw - 1) * 4) >> 2  # [B]
    b = p.shape[0]
    A = _BLOCK_WORDS
    if nw >= 4 * A:
        # Two-level block select: a flat one-hot costs n_words
        # reductions over ALL nw words (the dominant walker cost at
        # production row widths). Instead reshape the row into
        # [K, A]-word blocks, one-hot-select blocks bi and bi+1 (one
        # fused pass over the row, two tiny outputs), then run the
        # shifted-slice select inside the 2A-word superblock. Exact-
        # equivalent to the flat path for every position (including
        # the clamp-to-final-word caveat): superblock word j is row
        # word bi*A + j, and bi+1 == K one-hots to an all-zero block,
        # matching the zero padding the flat slices would read.
        K = -(-nw // A)
        blk = rows.words[:, : K * A].reshape(b, K, A)
        bi = base // A
        words = _two_level_words(blk, bi, base - bi * A, n_words)
        return _words_to_bytes(words), (jnp.maximum(p, 0) & 3)
    else:
        # Flat one-hot over the whole row — cheapest for short rows.
        # XLA fuses the iota comparison into the reduction, so each
        # word read streams only the word slice (exact by construction
        # — no dot, no floating point).
        src = rows.words
        oh = jax.lax.broadcasted_iota(jnp.int32, (b, nw), 1) == base[:, None]
        width = nw
    words = [
        jnp.sum(jnp.where(oh, src[:, k : k + width], jnp.uint32(0)), axis=1)
        for k in range(n_words)
    ]
    return _words_to_bytes(words), (jnp.maximum(p, 0) & 3)


def _two_level_words(
    blocks: jax.Array, bi: jax.Array, loc: jax.Array, n_words: int
) -> list[jax.Array]:
    """Two-level word-window select shared by :func:`_window` and
    :func:`_sup_window`: one-hot blocks ``bi`` and ``bi+1`` out of
    ``blocks`` uint32[B, K, A] (one fused pass, two tiny outputs),
    then the shifted-slice select of ``n_words`` words at word offset
    ``loc`` ∈ [0, A) within the 2A-word pair. ``bi+1 == K`` one-hots
    to an all-zero block (callers rely on it matching zero padding).
    Requires ``loc + n_words <= 2A`` (``n_words <= A + 1`` given
    ``loc < A`` — enforced by _window's _BLOCK_WORDS guard).
    """
    b, _k, A = blocks.shape
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (b, blocks.shape[1]), 1)
    lo = jnp.sum(
        jnp.where((iota_k == bi[:, None])[:, :, None], blocks, jnp.uint32(0)),
        axis=1,
    )
    hi = jnp.sum(
        jnp.where(
            (iota_k == bi[:, None] + 1)[:, :, None], blocks, jnp.uint32(0)
        ),
        axis=1,
    )
    pair = jnp.concatenate([lo, hi], axis=1)  # uint32[B, 2A]
    oh = jax.lax.broadcasted_iota(jnp.int32, (b, A), 1) == loc[:, None]
    return [
        jnp.sum(jnp.where(oh, pair[:, k : k + A], jnp.uint32(0)), axis=1)
        for k in range(n_words)
    ]


def _words_to_bytes(words: list[jax.Array]) -> jax.Array:
    """n_words per-lane uint32 words → int32[B, n_words*4] byte window."""
    ww = jnp.stack(words, axis=1)  # uint32[B, n_words]
    return jnp.stack(
        [(ww >> 24) & 0xFF, (ww >> 16) & 0xFF, (ww >> 8) & 0xFF, ww & 0xFF],
        axis=2,
    ).reshape(ww.shape[0], len(words) * 4).astype(jnp.int32)


def _sup_fetch(rows: _Rows, bi0: jax.Array) -> jax.Array:
    """Fetch a per-lane SUPERBLOCK: ``_SUP_BLOCKS`` consecutive
    ``_BLOCK_WORDS``-word blocks anchored at block index ``bi0``
    (superblock word ``j`` = row word ``bi0*_BLOCK_WORDS + j``).

    ONE fused pass over the row produces all ``_SUP_BLOCKS`` outputs —
    this is what lets the variable-count scans pay ~one HBM row pass
    per ~468 bytes of scanned region instead of one per TLV element.
    Blocks past the padded row one-hot to zero, matching the zero
    padding the flat path reads there.
    """
    b = bi0.shape[0]
    A = _BLOCK_WORDS
    K = -(-rows.n_words // A)
    blk = rows.words[:, : K * A].reshape(b, K, A)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (b, K), 1)
    parts = [
        jnp.sum(
            jnp.where((iota_k == bi0[:, None] + m)[:, :, None], blk,
                      jnp.uint32(0)),
            axis=1,
        )
        for m in range(_SUP_BLOCKS)
    ]
    return jnp.concatenate(parts, axis=1)  # uint32[B, _SUP_BLOCKS * A]


def _sup_window(sup: jax.Array, p: jax.Array, bi0: jax.Array, n_words: int):
    """:func:`_window`-contract byte window served FROM a superblock —
    pure VPU work on [B, 512] bytes, no row pass.

    ``p`` is the ROW byte position; the caller guarantees the window
    fits the superblock (``(p >> 2) - bi0*_BLOCK_WORDS + n_words <=
    _SUP_BLOCKS*_BLOCK_WORDS`` — the scan loops' ``can-process``
    condition). Returns ``(win int32[B, n_words*4], a int32[B])`` with
    window bytes identical to ``_window(rows, p, n_words)`` for every
    such position.
    """
    b = p.shape[0]
    A = _BLOCK_WORDS
    wloc = (p >> 2) - bi0 * A  # superblock word position
    sj = wloc // A
    words = _two_level_words(
        sup.reshape(b, _SUP_BLOCKS, A), sj, wloc - sj * A, n_words
    )
    return _words_to_bytes(words), (jnp.maximum(p, 0) & 3)


def _wbyte(win: jax.Array, rel: jax.Array) -> jax.Array:
    """Window byte at per-lane index ``rel``; 0 when out of range."""
    wb = win.shape[1]
    oh = jnp.arange(wb, dtype=jnp.int32)[None, :] == rel[:, None]
    return jnp.sum(jnp.where(oh, win, 0), axis=1)


def _read_header_w(win, a, delta, p, limit):
    """TLV header at row position ``p + delta`` read from ``win``
    (anchored at p) → (tag, content_len, header_len, ok).

    Supports short-form and long-form lengths up to 3 length octets
    (certificates are < 2^24 bytes). All int32[B].
    """
    rel = a + delta
    tag = _wbyte(win, rel)
    b0 = _wbyte(win, rel + 1)
    b1 = _wbyte(win, rel + 2)
    b2 = _wbyte(win, rel + 3)
    b3 = _wbyte(win, rel + 4)

    short = b0 < 0x80
    n_len = b0 - 0x80  # long-form octet count (valid when !short)
    long_ok = (b0 > 0x80) & (n_len <= 3)

    clen_long = jnp.where(
        n_len == 1, b1,
        jnp.where(n_len == 2, (b1 << 8) | b2, (b1 << 16) | (b2 << 8) | b3),
    )
    clen = jnp.where(short, b0, clen_long)
    hlen = jnp.where(short, 2, 2 + n_len)
    pos = p + delta
    ok = (short | long_ok) & (pos >= 0) & (pos + hlen + clen <= limit)
    return tag, clen, hlen, ok


def _header_at(rows: _Rows, p, limit):
    """Standalone header read: its own 3-word window at ``p``."""
    win, a = _window(rows, p, 3)
    return _read_header_w(win, a, jnp.zeros_like(p), p, limit)


def _parse_time_w(win, a, delta, p):
    """UTCTime/GeneralizedTime at row position ``p + delta`` (within the
    window anchored at p) → (epoch_hour, ok).

    UTCTime YYMMDDHHMMSSZ (RFC 5280 §4.1.2.5.1: 19YY if YY ≥ 50 else
    20YY); GeneralizedTime YYYYMMDDHHMMSSZ. Minutes/seconds are
    discarded — the ExpDate bucket truncates to the hour
    (/root/reference/storage/types.go:339-346).
    """
    tag, clen, hlen, hok = _read_header_w(win, a, delta, p, jnp.int32(2**30))
    is_utc = tag == 0x17
    is_gen = tag == 0x18
    ok = hok & (is_utc | is_gen) & jnp.where(is_utc, clen >= 11, clen >= 13)
    q = a + delta + hlen  # window-relative content start

    def digits2(off):
        return (_wbyte(win, off) - 0x30) * 10 + (_wbyte(win, off + 1) - 0x30)

    def is_digits2(off):
        b0 = _wbyte(win, off)
        b1 = _wbyte(win, off + 1)
        return ((b0 >= 0x30) & (b0 <= 0x39)
                & (b1 >= 0x30) & (b1 <= 0x39))

    yy = digits2(q)
    year_utc = jnp.where(yy >= 50, 1900 + yy, 2000 + yy)
    year_gen = yy * 100 + digits2(q + 2)
    year = jnp.where(is_utc, year_utc, year_gen)
    body = jnp.where(is_utc, q, q + 2)  # start of MMDDHH...
    month = digits2(body + 2)
    day = digits2(body + 4)
    hour = digits2(body + 6)
    # Every byte feeding the expiry bucket must be a genuine ASCII
    # digit — range checks alone let some mutated bytes alias into
    # plausible values, silently corrupting the (expDate, issuer,
    # serial) identity (caught by the walker/host mutation fuzz).
    # Minutes/seconds are not validated: the bucket truncates to the
    # hour (types.go:339-346), so they cannot affect identity.
    digits_ok = (is_digits2(q) & is_digits2(body + 2)
                 & is_digits2(body + 4) & is_digits2(body + 6)
                 & jnp.where(is_utc, True, is_digits2(q + 2)))
    ok = (ok & digits_ok
          & (month >= 1) & (month <= 12) & (day >= 1) & (day <= 31)
          & (hour <= 23))

    # Days-from-civil (Gregorian), valid for year ≥ 1583; all positive here.
    y = year - (month <= 2)
    era = y // 400
    yoe = y - era * 400
    mp = jnp.where(month > 2, month - 3, month + 9)
    doy = (153 * mp + 2) // 5 + day - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    days = era * 146097 + doe - 719468
    return days * 24 + hour, ok


def _scan_issuer_cn(rows: _Rows, name_off, name_end, hdr_ok0):
    """The CommonName (OID 2.5.4.3) of the issuer Name as Go's
    ``pkix.Name.FillFromRDNSequence`` fills ``cert.Issuer.CommonName``
    (the LAST CN attribute of the whole Name wins), or "cannot say".

    Name ::= SEQUENCE OF RelativeDistinguishedName;
    RDN ::= SET OF AttributeTypeAndValue;
    ATV ::= SEQUENCE { type OID, value ANY }.
    Returns ``(cn_off, cn_len)``: the value's content window, ``cn_len``
    0 when the Name has no CN (Go's ``""``), and ``cn_len`` -1 when the
    scan cannot say what Go would hold, so that the CN filter hands the
    lane to the exact host lane (``pipeline.local_lanes``: ``cn_undec``)
    instead of deciding it. The scan reads the FIRST attribute of each
    RDN and nothing behind it, so it says "cannot say" for:

    - an RDN that is not a SET of exactly one well-formed ATV (a
      multi-valued RDN may hold a CN behind its first attribute; a
      malformed one is the host parse's to reject);
    - a CN whose value is not of a type Go returns byte for byte
      (``core.der.RAW_STRING_TAGS``) or does not fill its ATV exactly;
    - a Name the scan did not walk to its end (more than ``MAX_RDNS``
      RDNs, or a header that does not parse).

    Every other Name is decided as Go decides it: two CN attributes give
    the second, an empty CN value gives ``""``. Runs as a superblock
    loop (see _scan_extensions): one row pass fetches each lane 512
    bytes; a typical issuer Name (3-6 RDNs, tens of bytes) scans in a
    single fetch.
    """
    b = name_off.shape[0]
    zero = jnp.zeros((b,), jnp.int32)
    supw = _SUP_BLOCKS * _BLOCK_WORDS
    stride = (supw - 8 - _BLOCK_WORDS) * 4
    outer_max = -(-(rows.n_words * 4) // stride) + 1

    def rdn_round(win, a, p, cn_off, cn_len, undec, alive, cnt, active):
        d0 = jnp.zeros_like(p)
        tag, clen, hlen, hok = _read_header_w(win, a, d0, p, name_end)
        da = hlen
        atag, aclen, ahlen, aok = _read_header_w(win, a, da, p, name_end)
        do = da + ahlen
        otag, oclen, ohlen, ook = _read_header_w(win, a, do, p, name_end)
        # One ATV that fills its SET, with an OID first: anything else
        # is not this scan's to read.
        plain = (
            hok & (tag == 0x31) & aok & (atag == 0x30)
            & (clen == ahlen + aclen) & ook & (otag == 0x06)
        )
        ro = a + do + ohlen
        is_cn = (
            active & plain & (oclen == 3)
            & (_wbyte(win, ro) == 0x55)
            & (_wbyte(win, ro + 1) == 0x04)
            & (_wbyte(win, ro + 2) == 0x03)
        )
        dv = do + ohlen + oclen
        vtag, vclen, vhlen, vok = _read_header_w(win, a, dv, p, name_end)
        # Content bytes that ARE Go's string; a BMPString is transcoded
        # and any other type leaves CommonName alone: not ours to compare.
        raw = functools.reduce(
            jnp.logical_or, [vtag == t for t in RAW_STRING_TAGS])
        good = vok & raw & (ohlen + oclen + vhlen + vclen == aclen)
        take = is_cn & good
        cn_off = jnp.where(take, p + dv + vhlen, cn_off)
        cn_len = jnp.where(take, vclen, cn_len)
        undec = undec | (active & ~plain) | (is_cn & ~good)
        p = jnp.where(active & hok, p + hlen + clen, p)
        cnt = cnt + (active & hok).astype(jnp.int32)
        alive = alive & jnp.where(active, hok, True)
        return p, cn_off, cn_len, undec, alive, cnt

    # Superblock loops (see _scan_extensions — same structure, same
    # window bytes per round as the old one-row-pass-per-RDN loop):
    # one row pass fetches each lane 512 bytes; RDNs are a few tens of
    # bytes, so a typical issuer Name scans in ONE fetch.
    def outer_cond(carry):
        r_out, _p, _co, _cl, _un, _alive, _cnt, live = carry
        return (r_out < outer_max) & jnp.any(live)

    def outer_body(carry):
        r_out, p, cn_off, cn_len, undec, alive, cnt, live = carry
        bi0 = p >> (2 + 4)
        sup = _sup_fetch(rows, bi0)

        def inner_cond(c):
            return jnp.any(c[-1])

        def inner_body(c):
            p, cn_off, cn_len, undec, alive, cnt, go = c
            win, a = _sup_window(sup, p, bi0, 8)
            p, cn_off, cn_len, undec, alive, cnt = rdn_round(
                win, a, p, cn_off, cn_len, undec, alive, cnt, go
            )
            wloc = (p >> 2) - bi0 * _BLOCK_WORDS
            go = (alive & (p < name_end) & (cnt < MAX_RDNS)
                  & (wloc <= supw - 8))
            return p, cn_off, cn_len, undec, alive, cnt, go

        # `live` doubles as the first round's go: a lane freshly
        # anchored at bi0 = p >> 6 always has wloc0 in [0, 16), so the
        # fit guard is trivially true.
        p, cn_off, cn_len, undec, alive, cnt, _go = jax.lax.while_loop(
            inner_cond, inner_body,
            (p, cn_off, cn_len, undec, alive, cnt, live)
        )
        live = alive & (p < name_end) & (cnt < MAX_RDNS)
        return r_out + 1, p, cn_off, cn_len, undec, alive, cnt, live

    live0 = hdr_ok0 & (name_off < name_end)
    (_r, p, cn_off, cn_len, undec, _alive, _cnt, _live) = jax.lax.while_loop(
        outer_cond, outer_body,
        (jnp.int32(0), name_off, zero, zero, jnp.zeros((b,), bool),
         hdr_ok0, zero, live0),
    )
    # A Name not walked to its end may hold a CN the scan never saw.
    undec = undec | (hdr_ok0 & (p != name_end))
    return jnp.where(undec, 0, cn_off), jnp.where(undec, -1, cn_len)


def _scan_extensions(rows: _Rows, ext_off, ext_end, alive0):
    """Walk SEQUENCE OF Extension for BasicConstraints CA + CRLDP
    presence.

    Superblock structure (round-4 rework): at production batch widths
    the early-exit never fires (some lane in a 2^20-lane batch always
    has many extensions), so the OLD one-row-pass-per-extension loop
    paid ~MAX_EXTS full HBM passes per batch. Now an OUTER loop
    fetches each lane a 512-byte superblock anchored at its position
    (ONE row pass, :func:`_sup_fetch`) and an INNER loop walks
    extensions entirely inside the superblock (:func:`_sup_window` —
    VPU-only); a lane waits for the next outer refetch only when its
    11-word window would cross the superblock edge. Each outer round
    therefore advances every active lane ≥ ~404 bytes (or to
    completion), so the row-pass count drops from ~MAX_EXTS to
    ≤ ceil(row/404) — the window bytes each round body sees are
    IDENTICAL to the old per-round ``_window`` read, so per-lane
    semantics (including the overrun and budget contracts) are
    unchanged. The per-lane extension budget stays MAX_EXTS (the old
    global round count bounded exactly the same thing).
    """
    b = ext_off.shape[0]
    false = jnp.zeros((b,), bool)
    zero = jnp.zeros((b,), jnp.int32)
    supw = _SUP_BLOCKS * _BLOCK_WORDS  # superblock words
    # Bytes a lane is guaranteed to traverse per outer round before its
    # window can cross the superblock edge (used for the outer budget).
    stride = (supw - 11 - _BLOCK_WORDS) * 4
    outer_max = -(-(rows.n_words * 4) // stride) + 1

    def outer_cond(carry):
        r_out, _p, _ca, _dp, _dpo, _dpl, _alive, _cnt, live = carry
        return (r_out < outer_max) & jnp.any(live)

    def outer_body(carry):
        r_out, p, is_ca, has_crldp, dp_off, dp_len, alive, cnt, live = carry
        bi0 = p >> (2 + 4)  # anchor block: p // (4 bytes * 16 words)
        sup = _sup_fetch(rows, bi0)

        def inner_cond(c):
            (_p, _ca, _dp, _dpo, _dpl, _alive, _cnt, go) = c
            return jnp.any(go)

        def inner_body(c):
            p, is_ca, has_crldp, dp_off, dp_len, alive, cnt, go = c
            win, a = _sup_window(sup, p, bi0, 11)
            (p, is_ca, has_crldp, dp_off, dp_len, alive, cnt) = _ext_round(
                win, a, p, ext_end,
                is_ca, has_crldp, dp_off, dp_len, alive, cnt, go,
            )
            wloc = (p >> 2) - bi0 * _BLOCK_WORDS
            go = (alive & (p < ext_end) & (cnt < MAX_EXTS)
                  & (wloc <= supw - 11))
            return p, is_ca, has_crldp, dp_off, dp_len, alive, cnt, go

        # `live` doubles as the first round's go: a lane freshly
        # anchored at bi0 = p >> 6 always has wloc0 in [0, 16), so the
        # fit guard is trivially true.
        (p, is_ca, has_crldp, dp_off, dp_len, alive, cnt, _go) = (
            jax.lax.while_loop(
                inner_cond, inner_body,
                (p, is_ca, has_crldp, dp_off, dp_len, alive, cnt, live),
            )
        )
        live = alive & (p < ext_end) & (cnt < MAX_EXTS)
        return (r_out + 1, p, is_ca, has_crldp, dp_off, dp_len, alive,
                cnt, live)

    live0 = alive0 & (ext_off < ext_end)
    (_r, p, is_ca, has_crldp, dp_off, dp_len, alive, _cnt, _live) = (
        jax.lax.while_loop(
            outer_cond, outer_body,
            (jnp.int32(0), ext_off, false, false, zero, zero, alive0,
             zero, live0),
        )
    )
    # Lanes still inside the window after exhausting the extension
    # budget — flag them (host lane) rather than silently missing a
    # trailing basicConstraints.
    exhausted = alive & (p < ext_end)
    return is_ca, has_crldp, dp_off, dp_len, alive & ~exhausted


def _ext_round(win, a, p, ext_end, is_ca, has_crldp, dp_off, dp_len,
               alive, cnt, active):
    """One extension parse against a window anchored at ``p`` — the
    original per-round body, window source abstracted out."""
    d0 = jnp.zeros_like(p)
    tag, clen, hlen, hok = _read_header_w(win, a, d0, p, ext_end)
    ext_ok = active & hok & (tag == 0x30)
    di = hlen
    otag, oclen, ohlen, ook = _read_header_w(win, a, di, p, ext_end)
    oid_ok = ext_ok & ook & (otag == 0x06) & (oclen == 3)
    ro = a + di + ohlen
    o0 = _wbyte(win, ro)
    o1 = _wbyte(win, ro + 1)
    o2 = _wbyte(win, ro + 2)
    is_bc = oid_ok & (o0 == 0x55) & (o1 == 0x1D) & (o2 == 0x13)
    is_dp = oid_ok & (o0 == 0x55) & (o1 == 0x1D) & (o2 == 0x1F)
    # optional BOOLEAN critical
    dc = di + ohlen + oclen
    ctag, cclen, chlen, cok = _read_header_w(win, a, dc, p, ext_end)
    has_crit = cok & (ctag == 0x01)
    dv = jnp.where(has_crit, dc + chlen + cclen, dc)
    vtag, vclen, vhlen, vok = _read_header_w(win, a, dv, p, ext_end)
    # extnValue must fit INSIDE its Extension frame (hlen + clen),
    # not merely inside the extension list — an inflated value
    # length would otherwise window into the next extension's
    # bytes. The whole LANE is rejected (host-lane fallback), in
    # lockstep with the host parser's DerError on the same input
    # (pinned by the walker/host mutation fuzz). The overrun check
    # uses a limit-free header re-read: a value whose end ALSO
    # crosses ext_end makes vok itself False, which must still
    # count as an overrun, not a silent skip (the list bound is a
    # superset of the frame bound). Same window bytes — pure
    # arithmetic, no extra gather.
    _vt2, vclen2, vhlen2, vok2 = _read_header_w(
        win, a, dv, p, jnp.int32(2**30)
    )
    overrun = ext_ok & vok2 & (dv + vhlen2 + vclen2 > hlen + clen)
    val_ok = vok & (vtag == 0x04) & ~overrun
    # BasicConstraints ::= SEQUENCE { cA BOOLEAN DEFAULT FALSE, ... }
    db = dv + vhlen
    btag, bclen, bhlen, bok = _read_header_w(win, a, db, p, ext_end)
    bc_seq_ok = val_ok & bok & (btag == 0x30)
    df = db + bhlen
    ftag, fclen, fhlen, fok = _read_header_w(win, a, df, p, ext_end)
    ca_flag = (
        bc_seq_ok & (bclen > 0) & fok & (ftag == 0x01) & (fclen == 1)
        & (_wbyte(win, a + df + fhlen) != 0)
    )
    is_ca = is_ca | (is_bc & ca_flag)
    take_dp = is_dp & val_ok & (dp_len == 0)
    dp_off = jnp.where(take_dp, p + dv + vhlen, dp_off)
    dp_len = jnp.where(take_dp, vclen, dp_len)
    has_crldp = has_crldp | (is_dp & val_ok)
    p = jnp.where(active & hok, p + hlen + clen, p)
    cnt = cnt + (active & hok).astype(jnp.int32)
    alive = alive & jnp.where(active, hok & ~overrun, True)
    return p, is_ca, has_crldp, dp_off, dp_len, alive, cnt


@functools.partial(jax.jit, static_argnames=("scan_issuer_cn",))
def parse_certs(
    data: jax.Array, length: jax.Array, scan_issuer_cn: bool = True
) -> ParsedCerts:
    """Extract map-stage fields from a batch of DER certificates.

    Args:
      data: uint8[B, L] zero-padded DER.
      length: int32[B] true byte length per lane.
      scan_issuer_cn: static — False skips the RDN scan entirely
        (several window reads per round); callers with no CN-prefix
        filter configured pass False and get cn_off/cn_len of 0.

    Returns a :class:`ParsedCerts`; lanes with ``ok=False`` must be
    re-parsed on the host (reference lane).
    """
    return parse_certs_rows(
        _pack_rows(data.astype(jnp.uint8)), length.astype(jnp.int32),
        scan_issuer_cn=scan_issuer_cn,
    )


def parse_certs_rows(
    rows: _Rows, length: jax.Array, scan_issuer_cn: bool = True
) -> ParsedCerts:
    """:func:`parse_certs` over pre-packed rows — callers that also
    extract serials (the fused ingest step) pack once and share."""
    length = length.astype(jnp.int32)
    b = length.shape[0]
    limit = length

    ok = length > 4
    zero = jnp.zeros((b,), jnp.int32)
    d0 = zero

    # The fixed walk pays ~one HBM row pass per window, so adjacent
    # headers are MERGED into shared windows wherever the next header
    # sits within reach for every well-formed certificate (11 windows
    # → 5). Reads that an adversarial length field pushes past a
    # merged window see zeros — each merge carries an explicit
    # in-window guard that routes such lanes to the exact host lane
    # instead of decoding the zeros (real certificates sit well inside
    # every guard; the guards exist so a crafted length can only cost
    # a host parse, never mis-extract).

    # -- window 1 (17 words = 68 bytes, anchored at 0): Certificate
    # SEQUENCE + TBSCertificate SEQUENCE + [0] version OPTIONAL +
    # serial INTEGER + signature AlgorithmIdentifier HEADER. Only the
    # alg header is read here (its frame is then skipped
    # arithmetically), so any AlgorithmIdentifier size — including
    # RSASSA-PSS's ~67-byte frame — stays on the device path. 68
    # bytes reach the alg header even for the 46-byte serial ceiling
    # (the widest serial the device schema accepts at all).
    w1 = 17 * 4  # window bytes — guards below must use this bound
    win, a = _window(rows, zero, w1 // 4)
    tag, clen, hlen, hok = _read_header_w(win, a, d0, zero, limit)
    ok &= hok & (tag == 0x30)
    d_tbs = hlen  # header lengths are ≤ 6, so every delta through the
    tag, clen, hlen, hok = _read_header_w(win, a, d_tbs, zero, limit)
    ok &= hok & (tag == 0x30)
    tbs_end = d_tbs + hlen + clen
    d = d_tbs + hlen  # ... version header stays in-window by bound
    tag, clen, hlen, hok = _read_header_w(win, a, d, zero, tbs_end)
    has_version = hok & (tag == 0xA0)
    dser = d + jnp.where(has_version, hlen + clen, 0)
    tag, clen, hlen, hok = _read_header_w(win, a, dser, zero, tbs_end)
    # Guard: the serial header's 5 bytes must all be in-window (an
    # adversarial version frame pushes dser out of reach).
    ok &= hok & (tag == 0x02) & (a + dser + 5 <= w1)
    serial_off = dser + hlen
    serial_len = clen
    d_alg = dser + hlen + clen
    tag, clen, hlen, hok = _read_header_w(win, a, d_alg, zero, tbs_end)
    ok &= hok & (tag == 0x30) & (a + d_alg + 5 <= w1)
    p = d_alg + hlen + clen  # past the whole AlgorithmIdentifier

    # -- issuer Name header: own small window anchored right at it.
    tag, clen, hlen, hok = _header_at(rows, p, tbs_end)
    ok &= hok & (tag == 0x30)
    issuer_off = p
    issuer_len_out = hlen + clen
    issuer_inner = issuer_off + hlen
    issuer_end = issuer_off + hlen + clen
    if scan_issuer_cn:
        cn_off, cn_len = _scan_issuer_cn(rows, issuer_inner, issuer_end, ok)
    else:  # CN filter disabled (static) — skip the RDN scan entirely
        cn_off = cn_len = jnp.zeros((b,), jnp.int32)
    p = issuer_end

    # -- window 3 (13 words): validity SEQUENCE { notBefore, notAfter }
    # + subject Name header (validity is ≤ ~36 bytes; the time parser's
    # strict digit checks reject any out-of-window zero reads).
    w3 = 13 * 4
    win, a = _window(rows, p, w3 // 4)
    tag, clen, hlen, hok = _read_header_w(win, a, d0, p, tbs_end)
    ok &= hok & (tag == 0x30)
    dnb = hlen
    nb_tag, nb_clen, nb_hlen, nb_ok = _read_header_w(win, a, dnb, p, tbs_end)
    ok &= nb_ok
    not_after_hour, t_ok = _parse_time_w(
        win, a, dnb + nb_hlen + nb_clen, p
    )
    ok &= t_ok
    d_subj = hlen + clen
    tag, clen, hlen, hok = _read_header_w(win, a, d_subj, p, tbs_end)
    ok &= hok & (tag == 0x30) & (a + d_subj + 5 <= w3)
    p = p + d_subj + hlen + clen  # past the subject Name

    # -- subjectPublicKeyInfo header: own window (the subject Name
    # length is unbounded, so no merge is possible).
    tag, clen, hlen, hok = _header_at(rows, p, tbs_end)
    ok &= hok & (tag == 0x30)
    spki_off = p
    spki_len = hlen + clen
    p = p + hlen + clen

    # -- window 4 (13 words): optional [1]/[2] UniqueID frames + [3]
    # EXPLICIT Extensions header + inner SEQUENCE header.
    w4 = 13 * 4
    win, a = _window(rows, p, w4 // 4)
    d = zero
    for _ in range(2):
        tag, clen, hlen, hok = _read_header_w(win, a, d, p, tbs_end)
        is_uid = hok & ((tag == 0x81) | (tag == 0x82) | (tag == 0xA1) | (tag == 0xA2))
        d = jnp.where(is_uid, d + hlen + clen, d)
    # Both the [3] header and the inner SEQUENCE header (≤ 6 + 5
    # bytes) must decode in-window. UniqueID frames large enough to
    # push them out (absent from real CT certificates) go host-side —
    # reading zeros there would silently classify the lane as
    # "no extensions".
    in_win = a + d + 11 <= w4
    tag, clen, hlen, hok = _read_header_w(win, a, d, p, tbs_end)
    has_ext = hok & (tag == 0xA3) & ((p + d) < tbs_end) & in_win
    # ANY trailing TBS bytes that are not a well-formed in-window [3]
    # frame route the lane to the exact host lane: the host parser
    # scans PAST frames it doesn't recognize (and tolerates a [3]
    # frame whose length overruns the TBS while its inner list is
    # intact), so silently deciding "no extensions" here would
    # mis-extract is_ca/CRLDP on exactly those certs (caught by the
    # round-7 sidecar/host mutation fuzz).
    ok &= has_ext | ((p + d) >= tbs_end)
    de = d + hlen
    etag, eclen, ehlen, eok = _read_header_w(win, a, de, p, tbs_end)
    ext_listed = has_ext & eok & (etag == 0x30)
    ok &= jnp.where(has_ext, eok & (etag == 0x30), True)
    ext_off = p + de + ehlen
    ext_end = jnp.where(ext_listed, p + de + ehlen + eclen,
                        jnp.zeros((b,), jnp.int32))
    is_ca, has_crldp, dp_off, dp_len, ext_ok = _scan_extensions(
        rows, ext_off, ext_end, ok
    )
    ok &= ext_ok

    return ParsedCerts(
        ok=ok,
        serial_off=jnp.where(ok, serial_off, 0),
        serial_len=jnp.where(ok, serial_len, 0),
        not_after_hour=jnp.where(ok, not_after_hour, 0),
        is_ca=is_ca & ok,
        has_crldp=has_crldp & ok,
        issuer_cn_off=cn_off,
        issuer_cn_len=jnp.where(ok, cn_len, 0),
        issuer_off=jnp.where(ok, issuer_off, 0),
        issuer_len=jnp.where(ok, issuer_len_out, 0),
        spki_off=jnp.where(ok, spki_off, 0),
        spki_len=jnp.where(ok, spki_len, 0),
        crldp_off=jnp.where(ok, dp_off, 0),
        crldp_len=jnp.where(ok, dp_len, 0),
    )


@functools.partial(jax.jit, static_argnames=("max_serial_bytes",))
def gather_serials(
    data: jax.Array, off: jax.Array, ln: jax.Array, max_serial_bytes: int = 46
) -> tuple[jax.Array, jax.Array]:
    """Extract serial content bytes into a fixed window — gather-free:
    one one-hot word window at ``off``, then a 4-way alignment select
    of static slices.

    Returns (serial uint8[B, max_serial_bytes] zero-padded,
    fits bool[B]). Lanes whose serial exceeds the window must use the
    host lane (real-world serials are ≤ 20 bytes per CABF; the window
    leaves slack for non-conforming logs).
    """
    return gather_serials_rows(
        _pack_rows(data.astype(jnp.uint8)), off, ln, max_serial_bytes
    )


def gather_serials_rows(
    rows: _Rows, off: jax.Array, ln: jax.Array, max_serial_bytes: int = 46
) -> tuple[jax.Array, jax.Array]:
    """:func:`gather_serials` over pre-packed rows (shared with
    :func:`parse_certs_rows` by the fused step)."""
    got = window_bytes_rows(rows, off, max_serial_bytes)
    mask = jnp.arange(max_serial_bytes, dtype=jnp.int32)[None, :] < ln[:, None]
    return jnp.where(mask, got, 0).astype(jnp.uint8), ln <= max_serial_bytes


def _dealign(win: jax.Array, a: jax.Array, n: int) -> jax.Array:
    """Window bytes [a, a+n) as int32[B, n] via a 4-way static-slice
    select (a = alignment ∈ {0,1,2,3})."""
    outs = [win[:, s : s + n] for s in range(4)]
    return jnp.where(
        (a == 0)[:, None], outs[0],
        jnp.where((a == 1)[:, None], outs[1],
                  jnp.where((a == 2)[:, None], outs[2], outs[3])),
    )


def window_bytes_rows(rows: _Rows, off: jax.Array, n: int) -> jax.Array:
    """Fixed-width byte window at per-lane ``off`` as int32[B, n] —
    gather-free (used by the CN-prefix filter)."""
    n_words = (3 + n + 3) // 4 + 1
    win, a = _window(rows, off, n_words)
    return _dealign(win, a, n)
