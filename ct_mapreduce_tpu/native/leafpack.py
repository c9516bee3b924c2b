"""Batch leaf decoding + packing on top of the native library.

``decode_raw_pages`` takes a chunk of get-entries responses, each kept
as the bytes the transport returned (:class:`EntryPage`, found by
:func:`scan_entries`) or given as two lists of base64 strings
(:class:`StrPage`; ``decode_raw_batch`` is the one-page form), and
produces the packed device arrays plus per-entry issuer DER — the
whole-host fast path between the HTTP client and the device
pipeline. Falls back to the pure-Python leaf codec
(:mod:`ct_mapreduce_tpu.ingest.leaf`) entry by entry when the native
library is unavailable, with identical results (the conformance tests
assert byte equality).
"""

from __future__ import annotations

import ctypes
from bisect import bisect_left, bisect_right
from dataclasses import dataclass
from typing import NamedTuple, Optional, Sequence

import numpy as np

from ct_mapreduce_tpu.native import load as load_native, note_return
from ct_mapreduce_tpu.telemetry import trace
from ct_mapreduce_tpu.telemetry.metrics import incr_counter

# Status codes — keep in sync with ctmr_native.cpp.
OK = 0
BAD_B64 = 1
BAD_LEAF = 2
UNSUPPORTED = 3
NO_CHAIN = 4
TOO_LONG = 5  # cert exceeds pad_len — a wider redecode can clear it
ISSUER_TOO_LONG = 6  # issuer DER >= 2 MiB — cert packed fine; a wider
# redecode is futile, the entry goes straight to the exact host lane


@dataclass
class DecodedBatch:
    """Packed batch + per-entry metadata for one get-entries response."""

    data: np.ndarray  # uint8[n, pad_len]
    length: np.ndarray  # int32[n]
    timestamp_ms: np.ndarray  # int64[n]
    entry_type: np.ndarray  # int32[n]
    _issuers: Optional[list]  # chain[0] DER per entry; None = lazy
    status: np.ndarray  # int32[n]
    # Issuer grouping (vectorized sink bookkeeping): entries with the
    # same chain[0] DER share a group id; group_issuers[g] is that DER.
    # -1 = no issuer. None when the producer didn't compute groups.
    issuer_group: Optional[np.ndarray] = None  # int32[n]
    group_issuers: Optional[list] = None  # list[bytes]

    @property
    def issuers(self) -> list:
        """Per-entry issuer DER list (duplicates share one bytes
        object). Materialized lazily — the vectorized sink path works
        from ``issuer_group``/``group_issuers`` and never pays the
        per-entry list build."""
        if self._issuers is None:
            self._issuers = [
                self.group_issuers[g] if g >= 0 else None
                for g in self.issuer_group.tolist()
            ]
        return self._issuers

    def ok_mask(self) -> np.ndarray:
        return self.status == OK


@dataclass
class Sidecar:
    """Per-lane pre-parsed identity fields for a packed batch — the
    host half of the pre-parsed ingest lane.

    Extracted by the native scalar port of the device DER walker
    (``ctmr_extract_sidecars``), so semantics are bit-exact with
    :func:`ct_mapreduce_tpu.ops.der_kernel.parse_certs` on every lane:
    ``ok == 0`` means the walker itself would reject the lane (it
    falls back to the device-walker path), and on ``ok`` lanes every
    field equals the walker's output (pinned by
    tests/test_preparsed.py's mutation fuzz). All arrays length n;
    offsets index into the packed row (cert DER at offset 0).
    """

    ok: np.ndarray  # uint8[n] — 0: route through the device walker
    serial_off: np.ndarray  # int32[n]
    serial_len: np.ndarray  # int32[n]
    not_after_hour: np.ndarray  # int32[n] epoch-hour bucket
    is_ca: np.ndarray  # uint8[n]
    has_crldp: np.ndarray  # uint8[n]
    cn_off: np.ndarray  # int32[n] — first issuer-CN value window
    cn_len: np.ndarray  # int32[n] (0 = no CN found)
    issuer_off: np.ndarray  # int32[n] — full issuer Name TLV
    issuer_len: np.ndarray  # int32[n]
    spki_off: np.ndarray  # int32[n]
    spki_len: np.ndarray  # int32[n]
    crldp_off: np.ndarray  # int32[n] — CRLDP extnValue content window
    crldp_len: np.ndarray  # int32[n]


def resolve_threads(n: int, threads: Optional[int] = None) -> int:
    """Effective intra-chunk native thread count for an ``n``-lane call.

    An explicit ``threads`` > 0 is honored as given, clamped only to
    the lane count (tests exercise the threaded stitch on tiny
    batches). Otherwise: ``CTMR_DECODE_THREADS`` env, then the legacy
    ``CTMR_DECODE_WORKERS``, then ``os.cpu_count()`` — auto-sized so
    every chunk keeps >= 2048 lanes (below that the split overhead
    exceeds the decode it parallelizes).
    """
    import os

    if threads is not None and int(threads) > 0:
        return max(1, min(int(threads), max(int(n), 1)))
    t = int(os.environ.get("CTMR_DECODE_THREADS", "0") or 0)
    if t <= 0:
        t = int(os.environ.get("CTMR_DECODE_WORKERS", "0") or 0)
    if t <= 0:
        t = os.cpu_count() or 1
    t = max(1, min(t, n // 2048)) if n >= 4096 else 1
    return max(1, min(t, 256))


def extract_sidecars(data: np.ndarray, length: np.ndarray,
                     threads: Optional[int] = None) -> Optional[Sidecar]:
    with trace.span("native.extract_sidecars", cat="native",
                    entries=int(data.shape[0])):
        return _extract_sidecars(data, length, threads)


def _extract_sidecars(data: np.ndarray, length: np.ndarray,
                      threads: Optional[int] = None) -> Optional[Sidecar]:
    """Pre-parsed sidecars for packed rows ``uint8[n, pad]`` +
    ``int32[n]`` lengths, or None when the native library is
    unavailable (callers then stay on the device-walker lane —
    there is deliberately no Python fallback: the contract is
    walker-exactness, and the walker itself is always available).

    ``threads`` > 1 splits the lane range across the native worker
    pool; every lane's outputs are written by exactly one chunk, so
    results are byte-identical to the serial pass."""
    import os

    if os.environ.get("CTMR_NATIVE", "1") == "0":
        return None
    lib = load_native()
    if lib is None:
        return None
    n = int(data.shape[0])
    data = np.ascontiguousarray(data, np.uint8)
    length = np.ascontiguousarray(length, np.int32)
    i32p = ctypes.POINTER(ctypes.c_int32)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    out_u8 = [np.zeros((n,), np.uint8) for _ in range(3)]
    out_i32 = [np.zeros((n,), np.int32) for _ in range(11)]
    ok, is_ca, has_crldp = out_u8
    (serial_off, serial_len, not_after_hour, cn_off, cn_len,
     issuer_off, issuer_len, spki_off, spki_len,
     crldp_off, crldp_len) = out_i32
    t = resolve_threads(n, threads)
    fn, extra = lib.ctmr_extract_sidecars, ()
    if t > 1 and getattr(lib, "has_mt", False):
        fn, extra = lib.ctmr_extract_sidecars_mt, (t,)
    fn(
        n, data.ctypes.data_as(u8p), data.shape[1],
        length.ctypes.data_as(i32p),
        ok.ctypes.data_as(u8p),
        serial_off.ctypes.data_as(i32p), serial_len.ctypes.data_as(i32p),
        not_after_hour.ctypes.data_as(i32p),
        is_ca.ctypes.data_as(u8p), has_crldp.ctypes.data_as(u8p),
        cn_off.ctypes.data_as(i32p), cn_len.ctypes.data_as(i32p),
        issuer_off.ctypes.data_as(i32p), issuer_len.ctypes.data_as(i32p),
        spki_off.ctypes.data_as(i32p), spki_len.ctypes.data_as(i32p),
        crldp_off.ctypes.data_as(i32p), crldp_len.ctypes.data_as(i32p),
        *extra,
    )
    return Sidecar(
        ok=ok, serial_off=serial_off, serial_len=serial_len,
        not_after_hour=not_after_hour, is_ca=is_ca, has_crldp=has_crldp,
        cn_off=cn_off, cn_len=cn_len,
        issuer_off=issuer_off, issuer_len=issuer_len,
        spki_off=spki_off, spki_len=spki_len,
        crldp_off=crldp_off, crldp_len=crldp_len,
    )


def extract_scts(data: np.ndarray, length: np.ndarray,
                 threads: Optional[int] = None,
                 issuer_key_hash: Optional[np.ndarray] = None):
    """Embedded-SCT tuples for packed rows: a
    :class:`ct_mapreduce_tpu.verify.sct.SctBatch` — the host half of
    the signature-verification lane (status / RFC 6962 precert digest /
    log id / r / s per lane). ``issuer_key_hash``: uint8[n, 32]
    per-lane SHA-256(issuer SPKI) signed into each digest (None →
    all-zero lanes, no issuer chain). Native scanner when available
    (``ctmr_extract_scts_v2``, lane-range threaded like the sidecar
    pass), else the bit-identical pure-python mirror — unlike the
    sidecar extractor there IS a python fallback, because the verify
    lane has no device walker to fall back onto."""
    from ct_mapreduce_tpu.verify.sct import SctBatch, extract_scts_np

    with trace.span("native.extract_scts", cat="native",
                    entries=int(data.shape[0])):
        import os

        lib = (None if os.environ.get("CTMR_NATIVE", "1") == "0"
               else load_native())
        if lib is None or not getattr(lib, "has_sct", False):
            return extract_scts_np(data, length, issuer_key_hash)
        n = int(data.shape[0])
        data = np.ascontiguousarray(data, np.uint8)
        length = np.ascontiguousarray(length, np.int32)
        out = SctBatch.empty(n)
        if n == 0:
            return out
        i64p = ctypes.POINTER(ctypes.c_int64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        if issuer_key_hash is None:
            ikh_ptr = ctypes.cast(None, u8p)
        else:
            issuer_key_hash = np.ascontiguousarray(
                issuer_key_hash, np.uint8)
            if issuer_key_hash.shape != (n, 32):
                raise ValueError(
                    f"issuer_key_hash must be uint8[{n}, 32], got "
                    f"{issuer_key_hash.shape}")
            ikh_ptr = issuer_key_hash.ctypes.data_as(u8p)
        t = resolve_threads(n, threads)
        fn, extra = lib.ctmr_extract_scts_v2, ()
        if t > 1 and getattr(lib, "has_mt", False):
            fn, extra = lib.ctmr_extract_scts_v2_mt, (t,)
        fn(
            n, data.ctypes.data_as(u8p), data.shape[1],
            length.ctypes.data_as(i32p),
            ikh_ptr,
            out.ok.ctypes.data_as(u8p),
            out.digest.ctypes.data_as(u8p),
            out.log_id.ctypes.data_as(u8p),
            out.timestamp_ms.ctypes.data_as(i64p),
            out.r.ctypes.data_as(u8p),
            out.s.ctypes.data_as(u8p),
            out.hash_alg.ctypes.data_as(u8p),
            out.sig_alg.ctypes.data_as(u8p),
            *extra,
        )
        return out


def _assign_gid(gid_of: dict, group_issuers: list, der: bytes) -> int:
    """Accumulating DER→group-id assignment (shared by every producer
    that merges issuer groups)."""
    gid = gid_of.get(der)
    if gid is None:
        gid = gid_of[der] = len(group_issuers)
        group_issuers.append(der)
    return gid


def _concat_b64(strings: Sequence[str]) -> tuple[bytes, np.ndarray]:
    offs = np.zeros((len(strings) + 1,), np.int64)
    parts = []
    pos = 0
    for i, s in enumerate(strings):
        b = s.encode("ascii") if isinstance(s, str) else s
        parts.append(b)
        pos += len(b)
        offs[i + 1] = pos
    return b"".join(parts), offs


def _gather_strs(lib, strings: Sequence[str]) -> Optional[tuple]:
    """``(ptr, off, items)`` for a column of ASCII ``str``: ``ptr[i]``
    is where ``items[i]``'s bytes lie and ``off`` their prefix sums —
    one native pass, no byte copied. ``items`` is this call's own list
    of the strings: the pointers are good for as long as it lives.
    None when some item is anything else (the caller joins)."""
    items = list(strings)
    ptr = np.empty((len(items),), np.uintp)
    off = np.empty((len(items) + 1,), np.int64)
    total = lib.gather_strs(
        items, len(items), ptr.ctypes.data,
        off.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        *lib.gather_pyapi)
    return None if total < 0 else (ptr, off, items)


@dataclass
class EntryPage:
    """One get-entries response kept as bytes. Entry i's base64
    ``leaf_input`` is ``body[li_off[i] : li_off[i] + li_len[i]]`` and
    its ``extra_data`` likewise (length 0: absent or empty). ``body`` is
    what the transport returned when the native scan took the page,
    else the page's strings laid end to end (:func:`page_of_strings`);
    either way nothing exists per entry but four integers.

    ``stats`` is what a decode sizes its buffers by: the longest
    ``leaf_input``, the longest ``extra_data`` and the two columns'
    total bytes, as Python ints. The scan leaves them; whoever builds
    a page without them has them computed here, once, on its own
    thread. ``row`` is the page's line of a chunk's page table
    (:func:`_page_table`): entry count, address of ``body``, addresses
    of the four columns. The addresses are those of the buffers the
    page was built over, which it keeps for as long as it lives: a
    page's columns are not written to or replaced afterwards."""

    body: bytes
    li_off: np.ndarray  # int64[n]
    li_len: np.ndarray  # int64[n]
    ed_off: np.ndarray  # int64[n]
    ed_len: np.ndarray  # int64[n]
    stats: Optional[tuple] = None  # (max li, max ed, sum li, sum ed)

    def __post_init__(self) -> None:
        self.li_off, self.li_len, self.ed_off, self.ed_len = cols = tuple(
            np.ascontiguousarray(c, np.int64) for c in (
                self.li_off, self.li_len, self.ed_off, self.ed_len))
        if len({c.shape for c in cols}) != 1 or cols[0].ndim != 1:
            raise ValueError("a page's four columns are one length")
        if self.stats is None:
            li, ed = self.li_len, self.ed_len
            self.stats = (int(li.max(initial=0)), int(ed.max(initial=0)),
                          int(li.sum()), int(ed.sum()))
        # bytes -> a read-only view, no copy: its address is the buffer's
        body = (np.frombuffer(self.body, np.uint8).ctypes.data
                if self.body else 0)
        self._held = (self.body, cols)  # what `row` points into
        self.row = (len(cols[0]), body, *(c.ctypes.data for c in cols))

    def __len__(self) -> int:
        return self.row[0]

    def leaf_input(self, i: int) -> bytes:
        off = int(self.li_off[i])
        return self.body[off:off + int(self.li_len[i])]

    def extra_data(self, i: int) -> bytes:
        off = int(self.ed_off[i])
        return self.body[off:off + int(self.ed_len[i])]

    def items(self) -> tuple[list, list]:
        """Both columns as lists of ``bytes``, one object an entry: for
        the lanes that want that (no native library, a stale one)."""
        body = self.body
        return tuple(
            [body[o:o + n] for o, n in zip(off.tolist(), ln.tolist())]
            for off, ln in ((self.li_off, self.li_len),
                            (self.ed_off, self.ed_len)))

    def max_leaf_input_len(self) -> int:
        return self.stats[0]


@dataclass
class StrPage:
    """One get-entries response as two lists of base64 strings, an item
    an entry: what tests and the audit driver hand the sink. The same reading interface as :class:`EntryPage`."""

    leaf_inputs: Sequence
    extra_datas: Sequence

    def __len__(self) -> int:
        return len(self.leaf_inputs)

    def leaf_input(self, i: int):
        return self.leaf_inputs[i]

    def extra_data(self, i: int):
        return self.extra_datas[i]

    def items(self) -> tuple:
        return self.leaf_inputs, self.extra_datas

    def max_leaf_input_len(self) -> int:
        return max(map(len, self.leaf_inputs), default=0)


def scan_entries(body: bytes, cap: int) -> Optional[EntryPage]:
    """The page a get-entries response ``body`` of at most ``cap``
    entries is, found by one native pass with the GIL released; None
    where the scanner says the bytes are not its kind (an escape, a
    byte outside ASCII, a member that is no string, more than ``cap``
    entries, anything malformed) or the library has no scanner. The
    caller then parses the body as JSON, which accepts or raises as it
    always has. The same call leaves the page's ``stats`` (a library
    from before that: the page computes them)."""
    lib = load_native()
    if lib is None or not getattr(lib, "has_scan", False):
        return None
    cols = np.empty((4, max(int(cap), 1)), np.int64)
    i64p = ctypes.POINTER(ctypes.c_int64)
    args = (body, len(body), cols.shape[1],
            *(cols[k].ctypes.data_as(i64p) for k in range(4)))
    sizes = None
    if getattr(lib, "has_pages", False):
        sizes = (ctypes.c_int64 * 4)()
        n = lib.ctmr_scan_entries_stats(*args, sizes)
    else:
        n = lib.ctmr_scan_entries(*args)
    if trace.enabled():
        note_return(lib)
    if n < 0:
        return None
    return EntryPage(body, *(cols[k, :n] for k in range(4)),
                     stats=sizes and tuple(sizes))


def page_of_strings(leaf_inputs: Sequence[str],
                    extra_datas: Sequence[str]) -> EntryPage:
    """The same page form from the strings a JSON parser returned:
    laid end to end in one buffer, every ``leaf_input`` then every
    ``extra_data``. A character outside ASCII stays in as its UTF-8
    bytes (no base64, so that entry's status says so)."""
    n = len(leaf_inputs)
    parts = []
    for col in (leaf_inputs, extra_datas):
        for s in col:
            if not isinstance(s, str):  # a JSON number, null, object
                raise TypeError(
                    f"get-entries value is {type(s).__name__}, not a string")
            parts.append(s.encode("utf-8", "surrogatepass"))
    lens = np.fromiter(map(len, parts), np.int64, 2 * n)
    offs = np.cumsum(lens) - lens
    return EntryPage(b"".join(parts), offs[:n], lens[:n], offs[n:], lens[n:])


class _B64Columns(NamedTuple):
    """Both base64 columns as the native decoder takes them. Entry i of
    a column is ``off[i+1] - off[i]`` bytes; ``li``/``ed`` are the
    joined buffers (``bytes``) when ``owner`` is None, else arrays of
    pointers into the bodies and strings that ``owner`` keeps alive.
    The properties are what :func:`_decode_native` sizes its buffers
    by, under the names :class:`_PageTable` gives them."""

    li: object
    li_off: np.ndarray  # int64[n + 1]
    ed: object
    ed_off: np.ndarray  # int64[n + 1]
    owner: Optional[list]

    @property
    def n(self) -> int:
        return len(self.li_off) - 1

    @property
    def nbytes(self) -> int:
        return int(self.li_off[-1] + self.ed_off[-1])

    @property
    def longest(self) -> tuple[int, int]:
        """The longest ``leaf_input`` and the longest ``extra_data``."""
        return tuple(int(np.max(np.diff(off))) if self.n else 0
                     for off in (self.li_off, self.ed_off))

    def ed_bytes(self, lo: int, hi: int) -> int:
        """The ``extra_data`` bytes of lanes ``[lo, hi)``."""
        return int(self.ed_off[hi] - self.ed_off[lo])


class _PageTable(NamedTuple):
    """A chunk whose every page is an :class:`EntryPage`, as
    ``ctmr_decode_entries_pages`` takes it: a row a page (``EntryPage.
    row``), from which the call builds the per-entry columns itself,
    GIL released. What :func:`_decode_native` has to know before the
    call comes from the pages' ``stats``: no per-entry array exists on
    this side. ``owner`` is the pages, alive for the call."""

    rows: np.ndarray  # int64[pages, 6]
    n: int
    starts: list  # page p holds lanes [starts[p], starts[p + 1])
    ed_sums: list  # prefix sums of the pages' extra_data bytes
    longest: tuple  # (leaf_input, extra_data)
    nbytes: int
    owner: list

    def ed_bytes(self, lo: int, hi: int) -> int:
        """An upper bound on the ``extra_data`` bytes of lanes ``[lo,
        hi)``: those of every page that has a lane among them (a page
        is 512 lanes where a thread's range is some 5,000)."""
        if hi <= lo:
            return 0
        first = bisect_right(self.starts, lo) - 1
        last = bisect_left(self.starts, hi) - 1
        return self.ed_sums[last + 1] - self.ed_sums[first]


def _page_table(pages: Sequence, n: int) -> Optional[_PageTable]:
    """The chunk's page table, or None where a page is no
    :class:`EntryPage`. A loop over attributes and Python ints and one
    ``np.array`` of them: nothing here lets the GIL go, and nothing
    grows with the entries."""
    rows = []
    starts, ed_sums = [0], [0]
    max_li = max_ed = nbytes = 0
    for page in pages:
        if not isinstance(page, EntryPage):
            return None
        rows.append(page.row)
        li, ed, li_sum, ed_sum = page.stats
        max_li, max_ed = max(max_li, li), max(max_ed, ed)
        nbytes += li_sum + ed_sum
        starts.append(starts[-1] + page.row[0])
        ed_sums.append(ed_sums[-1] + ed_sum)
    if starts[-1] != n:  # the outputs were sized by n
        raise ValueError(f"pages hold {starts[-1]} entries, not {n}")
    return _PageTable(np.array(rows, np.int64).reshape(-1, 6), n, starts,
                      ed_sums, (max_li, max_ed), nbytes, list(pages))


def _flatten(pages: Sequence) -> tuple[list, list]:
    """A chunk's pages as two lists, one item an entry."""
    lis: list = []
    eds: list = []
    for page in pages:
        li, ed = page.items()
        lis.extend(li)
        eds.extend(ed)
    return lis, eds


def _ptr_columns(lib, pages: Sequence, n: int) -> tuple[_B64Columns, int]:
    """Pointer columns over a chunk's pages, no base64 byte moved: a
    page kept as bytes gives ``address of body + offsets`` (one numpy
    expression a column), a :class:`StrPage` of ASCII ``str`` is read where
    the strings lie (:func:`_gather_strs`), and a page of anything else
    is joined first, raising what that join always raised. Returns the
    columns and how many pages were joined."""
    ptr = np.empty((2, n), np.uintp)
    off = np.zeros((2, n + 1), np.int64)
    owner: list = []
    joined = 0
    a = 0

    def place(col: int, b: int, buf: bytes, offs, lens) -> None:
        # bytes -> a read-only view, no copy: its address is the buffer's
        addr = np.frombuffer(buf, np.uint8).ctypes.data if buf else 0
        np.add(offs, addr, out=ptr[col, a:b], casting="unsafe")
        off[col, a + 1:b + 1] = lens
        owner.append(buf)

    for page in pages:
        b = a + len(page)
        if isinstance(page, EntryPage):
            place(0, b, page.body, page.li_off, page.li_len)
            place(1, b, page.body, page.ed_off, page.ed_len)
            a = b
            continue
        li = _gather_strs(lib, page.leaf_inputs)
        ed = _gather_strs(lib, page.extra_datas) if li else None
        if ed:
            for col, (p, o, items) in enumerate((li, ed)):
                ptr[col, a:b] = p
                off[col, a + 1:b + 1] = np.diff(o)
                owner.append(items)
        else:
            joined += 1
            for col, (buf, o) in enumerate(map(_concat_b64, page.items())):
                place(col, b, buf, o[:-1], np.diff(o))
        a = b
    np.cumsum(off, axis=1, out=off)
    return _B64Columns(ptr[0], off[0], ptr[1], off[1], owner), joined


def _count_pages(pages: Sequence, walked: int) -> None:
    """``decode.pages_tabled`` / ``decode.pages_walked``, both on every
    chunk, 0 included: a program that counts them says so each time."""
    incr_counter("decode", "pages_tabled", value=float(len(pages) - walked))
    incr_counter("decode", "pages_walked", value=float(walked))


def _b64_columns(lib, pages: Sequence, n: int):
    """A chunk's base64 (``n`` entries) as the native call reads it,
    GIL held; each of ``pages`` is an :class:`EntryPage` or a
    :class:`StrPage`. What the chunk is decides the form, no setting
    does. Every page an :class:`EntryPage` and a library that takes a
    page table: the table (:func:`_page_table`), and the per-entry
    columns are the native call's to build. Else the columns are built
    here, page by page (``walked`` counts those pages): nothing is
    copied where the decoder can be pointed at the bytes
    (:func:`_ptr_columns`); ``joined`` counts the pages that had to be
    encoded and joined as before (``bytes`` items, a non-ASCII
    ``str``), and with a library that reads no pointer columns that is
    the whole chunk, one buffer a column."""
    with trace.span("decode.concat_b64", cat="decode") as sp:
        joined, walked = 0, len(pages)
        cols = (_page_table(pages, n)
                if getattr(lib, "has_pages", False) else None)
        if cols is not None:
            walked = 0
        elif getattr(lib, "has_strs", False):
            cols, joined = _ptr_columns(lib, pages, n)
        else:
            lis, eds = _flatten(pages)
            cols = _B64Columns(*_concat_b64(lis), *_concat_b64(eds),
                               owner=None)
            joined = len(pages)
        sp.set(bytes=cols.nbytes, joined=joined, pages=len(pages),
               walked=walked)
    _count_pages(pages, walked)
    return cols


def decode_raw_batch(
    leaf_inputs: Sequence[str],
    extra_datas: Sequence[str],
    pad_len: int,
    workers: Optional[int] = None,
    threads: Optional[int] = None,
) -> DecodedBatch:
    """One page given as two lists of base64 strings: see
    :func:`decode_raw_pages`."""
    return decode_raw_pages([StrPage(leaf_inputs, extra_datas)], pad_len,
                            workers=workers, threads=threads)


def decode_raw_pages(
    pages: Sequence,
    pad_len: int,
    workers: Optional[int] = None,
    threads: Optional[int] = None,
) -> DecodedBatch:
    n = sum(map(len, pages))
    with trace.span("native.decode_batch", cat="native",
                    entries=n, pad=int(pad_len)):
        return _decode_raw_pages(pages, n, pad_len,
                                 workers=workers, threads=threads)


def _decode_raw_pages(
    pages: Sequence,
    n: int,
    pad_len: int,
    workers: Optional[int] = None,
    threads: Optional[int] = None,
) -> DecodedBatch:
    """Decode a chunk of get-entries responses (``n`` entries in all,
    each page an :class:`EntryPage` or a :class:`StrPage`) into packed
    device arrays, as if they were one response.

    ``threads`` > 1 splits the batch across the native library's
    persistent worker pool — one ctypes call, lane ranges decoded in
    parallel inside C++ with the GIL released. Measured on the
    benchmark's TPU v5e host (PERF.md §5, PR 39: 65,536 entries in 128
    scanned pages, pad 2048, 13 threads): the call takes 40-41 ms, the
    page table before it 0.3 ms, and the Python after it — allocating
    rows, grouping issuers — 5-6 ms with the GIL held beside one
    downloader, 12-19 ms beside three or under queries (the wait for
    the GIL is most of the difference). ``workers`` is the legacy alias
    for the same knob (used when ``threads`` is unset). Default: the
    :func:`resolve_threads` policy (``CTMR_DECODE_THREADS`` env →
    ``CTMR_DECODE_WORKERS`` → ``os.cpu_count()``, bounded so each
    chunk keeps >= 2048 entries).

    Determinism: per-lane outputs are written by exactly one chunk
    into disjoint ranges, and the chunks' issuer spans merge by DER
    bytes in chunk (= lane) order, so the returned
    :class:`DecodedBatch` is byte-identical across thread counts
    (pinned by tests/test_decode_threads.py).
    """
    import os

    # CTMR_NATIVE=0 forces the pure-Python lane (read per call, not at
    # load: a test may flip it mid-process; results are byte-identical
    # by the conformance suite).
    lib = (None if os.environ.get("CTMR_NATIVE", "1") == "0"
           else load_native())
    if lib is None:
        _count_pages(pages, len(pages))
        return _decode_python(*_flatten(pages), pad_len)

    t = resolve_threads(n, threads if threads else workers)
    if not getattr(lib, "has_mt", False):
        t = 1  # stale prebuilt library without the pool entry points

    data = np.zeros((n, pad_len), np.uint8)
    length = np.zeros((n,), np.int32)
    ts = np.zeros((n,), np.int64)
    ety = np.zeros((n,), np.int32)
    status = np.zeros((n,), np.int32)
    out = (data, length, ts, ety, status)

    cols = _b64_columns(lib, pages, n)
    span = _decode_native(lib, cols, pad_len, out, t)
    if span is None and t > 1:
        # A chunk's issuer slice overflowed (pathologically skewed
        # extra_data) — retry serial with the undivided buffer.
        t = 1
        span = _decode_native(lib, cols, pad_len, out, t)
    if span is None:  # issuer scratch overflow — impossible by sizing
        return _decode_python(*_flatten(pages), pad_len)
    group, group_issuers = _issuer_groups(*span, chunks=t)
    return DecodedBatch(data, length, ts, ety, None, status,
                        issuer_group=group, group_issuers=group_issuers)


def _issuer_groups(
    issuer_off: np.ndarray,
    issuer_len: np.ndarray,
    issuer_buf: np.ndarray,
    chunks: int = 1,
) -> tuple:
    """Group a whole batch's entries by issuer DER: ``(group,
    group_issuers)`` in first-appearance order, as the pure-Python
    lane's dict gives them.

    The native decoder dedups identical issuer DERs into shared
    (off, len) spans — per chunk, each chunk appending into its own
    slice of ``issuer_buf`` in lane order — so one numpy unique over
    the span ids visits the spans in (chunk, first appearance) order,
    and only a DER that several chunks (or a full dedup table) wrote
    twice is merged by its bytes: per-span work, never per-entry.
    Each span is sliced out on its own; nothing the size of the
    buffer is copied."""
    with trace.span("decode.issuer_groups", cat="decode",
                    chunks=int(chunks)) as sp:
        group = np.full((len(issuer_off),), -1, np.int32)
        group_issuers: list = []
        has = issuer_len > 0
        nbytes = 0
        if has.any():
            # off < issuer_cap (< 2^42), len < 2^21 (pad-scale certs):
            # the combined key fits int64 losslessly.
            combo = issuer_off[has] * (1 << 21) + issuer_len[has]
            uniq, inverse = np.unique(combo, return_inverse=True)
            remap = np.empty((len(uniq),), np.int32)
            gid_of: dict = {}
            for g, c in enumerate(uniq.tolist()):
                off, ln = c >> 21, c & ((1 << 21) - 1)
                der = issuer_buf[off:off + ln].tobytes()
                nbytes += ln
                remap[g] = _assign_gid(gid_of, group_issuers, der)
            group[has] = remap[inverse]
        sp.set(groups=len(group_issuers), bytes=nbytes)
    return group, group_issuers


def _decode_native(
    lib,
    cols,
    pad_len: int,
    out: tuple,
    threads: int,
) -> Optional[tuple]:
    """The one native call of a batch, writing into caller-provided
    row views ``out = (data, length, ts, ety, status)``: ``threads``
    contiguous lane ranges decoded in parallel on the native worker
    pool (1 = the serial pass). Returns the batch's ``(issuer_off,
    issuer_len, issuer_buf)`` — identical DERs of one chunk share one
    span; spans carry GLOBAL offsets into the shared buffer, chunk
    ``t``'s within its slice ``[t * iss_each, (t + 1) * iss_each)`` —
    or None when a chunk's issuer slice overflowed. ``cols`` is a
    :class:`_B64Columns` or a :class:`_PageTable`."""
    n = cols.n
    data, length, ts, ety, status = out
    issuer_off = np.zeros((n,), np.int64)
    issuer_len = np.zeros((n,), np.int32)
    # Chunk bounds mirror the C split exactly: lane [n*t//T, n*(t+1)//T).
    bounds = [(n * t) // threads for t in range(threads + 1)]
    # Each chunk's issuer slice must hold that chunk's chain bytes;
    # its base64 extra_data length (or, from a page table, that of the
    # pages its lanes lie in) is a safe upper bound on them (issuer
    # chain certs are ~1-2 KB).
    iss_each = max(
        4096,
        max(cols.ed_bytes(bounds[t], bounds[t + 1]) for t in range(threads)),
    )
    issuer_buf = np.zeros((threads * iss_each,), np.uint8)
    # A chunk's scratch must hold one decoded leaf_input + extra_data.
    scratch_each = max(sum(cols.longest) + 64, 4096)
    scratch = np.zeros((threads * scratch_each,), np.uint8)
    chunk_used = np.zeros((threads,), np.int64)

    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    pool = (threads, chunk_used.ctypes.data_as(i64p))
    if isinstance(cols, _PageTable):
        fn = lib.ctmr_decode_entries_pages
        b64 = (len(cols.rows), cols.rows.ctypes.data, n)
    else:
        if cols.owner is not None:
            fn, li, ed = (lib.ctmr_decode_entries_strs,
                          cols.li.ctypes.data, cols.ed.ctypes.data)
        elif threads > 1:
            fn, li, ed = lib.ctmr_decode_entries_mt, cols.li, cols.ed
        else:  # also what a stale library without the pool still has
            fn, li, ed, pool = lib.ctmr_decode_entries, cols.li, cols.ed, ()
        b64 = (n, li, cols.li_off.ctypes.data_as(i64p),
               ed, cols.ed_off.ctypes.data_as(i64p))
    # The one call that releases the GIL; what stands around it in
    # native.decode_batch is Python.
    with trace.span("decode.native_call", cat="decode",
                    threads=int(threads), pad=int(pad_len)):
        rc = fn(
            *b64,
            pad_len,
            data.ctypes.data_as(u8p), length.ctypes.data_as(i32p),
            ts.ctypes.data_as(i64p), ety.ctypes.data_as(i32p),
            issuer_buf.ctypes.data_as(u8p), issuer_buf.shape[0],
            issuer_off.ctypes.data_as(i64p),
            issuer_len.ctypes.data_as(i32p),
            status.ctypes.data_as(i32p),
            scratch.ctypes.data_as(u8p), scratch_each,
            *pool,
        )
        if trace.enabled():
            note_return(lib)
    if rc < 0:
        return None
    return issuer_off, issuer_len, issuer_buf


def pack_ders(ders: Sequence[bytes], pad_len: int,
              threads: Optional[int] = None):
    with trace.span("native.pack_ders", cat="native", entries=len(ders)):
        return _pack_ders(ders, pad_len, threads)


def _pack_ders(ders: Sequence[bytes], pad_len: int,
               threads: Optional[int] = None):
    """Pack pre-decoded DER blobs into the ``[n, pad_len]`` device
    layout via the native packer (parallel over lane ranges when
    ``threads`` > 1); returns ``(data, length, ok, packed_count)`` or
    None when the native library is unavailable."""
    import os

    if os.environ.get("CTMR_NATIVE", "1") == "0":
        return None
    lib = load_native()
    if lib is None:
        return None
    n = len(ders)
    blob = np.frombuffer(b"".join(ders) or b"\x00", np.uint8)
    off = np.zeros((n + 1,), np.int64)
    if n:
        off[1:] = np.cumsum([len(d) for d in ders])
    data = np.zeros((n, pad_len), np.uint8)
    length = np.zeros((n,), np.int32)
    ok = np.zeros((n,), np.uint8)
    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    t = resolve_threads(n, threads)
    if t > 1 and getattr(lib, "has_mt", False):
        packed = lib.ctmr_pack_ders_mt(
            n, blob.ctypes.data_as(u8p), off.ctypes.data_as(i64p),
            pad_len,
            data.ctypes.data_as(u8p), length.ctypes.data_as(i32p),
            ok.ctypes.data_as(u8p), t,
        )
    else:
        packed = lib.ctmr_pack_ders(
            n, blob.ctypes.data_as(u8p), off.ctypes.data_as(i64p),
            pad_len,
            data.ctypes.data_as(u8p), length.ctypes.data_as(i32p),
            ok.ctypes.data_as(u8p),
        )
    return data, length, ok, int(packed)


def _decode_python(
    leaf_inputs: Sequence[str], extra_datas: Sequence[str], pad_len: int
) -> DecodedBatch:
    """Pure-Python fallback with identical semantics."""
    import base64
    import binascii

    from ct_mapreduce_tpu.ingest import leaf as leaflib

    n = len(leaf_inputs)
    data = np.zeros((n, pad_len), np.uint8)
    length = np.zeros((n,), np.int32)
    ts = np.zeros((n,), np.int64)
    ety = np.zeros((n,), np.int32)
    status = np.zeros((n,), np.int32)
    issuers: list[Optional[bytes]] = [None] * n
    for i in range(n):
        try:
            li = base64.b64decode(leaf_inputs[i], validate=True)
            ed = base64.b64decode(extra_datas[i] or "", validate=True)
        except (binascii.Error, ValueError):
            status[i] = BAD_B64
            continue
        try:
            e = leaflib.decode_entry(i, li, ed)
        except leaflib.LeafDecodeError as err:
            status[i] = (
                UNSUPPORTED if "unsupported" in str(err)
                or "unknown entry_type" in str(err) else BAD_LEAF
            )
            continue
        ts[i] = e.timestamp_ms
        ety[i] = e.entry_type
        if len(e.cert_der) > pad_len:
            status[i] = TOO_LONG
            continue
        data[i, : len(e.cert_der)] = np.frombuffer(e.cert_der, np.uint8)
        length[i] = len(e.cert_der)
        if not e.issuer_der:  # absent OR zero-length chain[0]
            status[i] = NO_CHAIN
        elif len(e.issuer_der) >= (1 << 21):
            # Native-path parity: pathological >=2 MiB issuer DERs are
            # routed down the exact host lane (span-packing bound). The
            # cert row stays packed, exactly like the native decoder
            # (which packs before its issuer-length check) — hence the
            # dedicated status: callers must not redecode wider for it.
            status[i] = ISSUER_TOO_LONG
        else:
            issuers[i] = e.issuer_der
    # Grouping for the vectorized sink path (dict-based — this is the
    # no-native fallback, already per-entry Python).
    group = np.full((n,), -1, np.int32)
    group_issuers: list = []
    gid_of: dict = {}
    for i, der in enumerate(issuers):
        if der is not None:
            group[i] = _assign_gid(gid_of, group_issuers, der)
    return DecodedBatch(data, length, ts, ety, issuers, status,
                        issuer_group=group, group_issuers=group_issuers)
