"""Native host-side ingest accelerator (C++ via ctypes).

Loads (building on first use, cached beside the source) the compiled
batch decoder in :file:`ctmr_native.cpp`. Everything degrades to the
pure-Python lanes when no compiler is available — the native path is a
throughput optimization, never a correctness dependency.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import time
from typing import Optional

import numpy as np

from ct_mapreduce_tpu.telemetry import trace

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "ctmr_native.cpp")
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_LOAD_FAILED = False
# core.packing.MAX_SERIAL_BYTES, which ctmr_fingerprints has compiled
# in (core/ imports this package, not the other way round).
FP_WINDOW_BYTES = 46


def _so_path() -> str:
    # Cache beside the source when writable, else in ~/.cache.
    if os.access(_HERE, os.W_OK):
        return os.path.join(_HERE, "libctmr_native.so")
    cache = os.path.join(
        os.path.expanduser("~"), ".cache", "ct_mapreduce_tpu"
    )
    os.makedirs(cache, exist_ok=True)
    return os.path.join(cache, "libctmr_native.so")


def _build(so: str) -> bool:
    # Compile to a temp path and rename atomically — a concurrent
    # process must never dlopen a half-written .so.
    tmp = f"{so}.build.{os.getpid()}"
    for cxx in ("g++", "c++", "clang++"):
        try:
            res = subprocess.run(
                [cxx, "-O3", "-shared", "-fPIC", "-std=c++17",
                 "-pthread", "-o", tmp, _SRC],
                capture_output=True, timeout=240,
            )
        except (FileNotFoundError, subprocess.TimeoutExpired):
            continue
        if res.returncode == 0:
            os.replace(tmp, so)
            return True
    if os.path.exists(tmp):
        os.unlink(tmp)
    return False


def load() -> Optional[ctypes.CDLL]:
    """The shared library, or None when unavailable (no compiler)."""
    global _LIB, _LOAD_FAILED
    if _LIB is not None or _LOAD_FAILED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _LOAD_FAILED:
            return _LIB
        so = _so_path()
        if (not os.path.exists(so)
                or os.path.getmtime(so) < os.path.getmtime(_SRC)):
            if not _build(so):
                _LOAD_FAILED = True
                return None
        try:
            lib = ctypes.CDLL(so)
        except OSError:
            _LOAD_FAILED = True
            return None
        i64p = ctypes.POINTER(ctypes.c_int64)
        i32p = ctypes.POINTER(ctypes.c_int32)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.ctmr_decode_entries.restype = ctypes.c_int64
        lib.ctmr_decode_entries.argtypes = [
            ctypes.c_int64,
            ctypes.c_char_p, i64p,
            ctypes.c_char_p, i64p,
            ctypes.c_int64,
            u8p, i32p,
            i64p, i32p,
            u8p, ctypes.c_int64,
            i64p, i32p,
            i32p,
            u8p, ctypes.c_int64,
        ]
        lib.ctmr_extract_sidecars.restype = None
        lib.ctmr_extract_sidecars.argtypes = [
            ctypes.c_int64,
            u8p, ctypes.c_int64, i32p,
            u8p,
            i32p, i32p,
            i32p,
            u8p, u8p,
            i32p, i32p,
            i32p, i32p,
            i32p, i32p,
            i32p, i32p,
        ]
        lib.ctmr_pack_ders.restype = ctypes.c_int64
        lib.ctmr_pack_ders.argtypes = [
            ctypes.c_int64,
            u8p, i64p,
            ctypes.c_int64,
            u8p, i32p, u8p,
        ]
        # Multi-threaded entry points (worker-pool lane-range split).
        # A prebuilt .so from before the pool existed may lack them —
        # the mtime check rebuilds from source when possible, but a
        # compiler-less host with a stale cached library must still
        # load: callers check `has_mt` and stay on the serial paths.
        try:
            lib.ctmr_decode_entries_mt.restype = ctypes.c_int64
            lib.ctmr_decode_entries_mt.argtypes = [
                ctypes.c_int64,
                ctypes.c_char_p, i64p,
                ctypes.c_char_p, i64p,
                ctypes.c_int64,
                u8p, i32p,
                i64p, i32p,
                u8p, ctypes.c_int64,
                i64p, i32p,
                i32p,
                u8p, ctypes.c_int64,
                ctypes.c_int64, i64p,
            ]
            lib.ctmr_extract_sidecars_mt.restype = None
            lib.ctmr_extract_sidecars_mt.argtypes = [
                ctypes.c_int64,
                u8p, ctypes.c_int64, i32p,
                u8p,
                i32p, i32p,
                i32p,
                u8p, u8p,
                i32p, i32p,
                i32p, i32p,
                i32p, i32p,
                i32p, i32p,
                ctypes.c_int64,
            ]
            lib.ctmr_pack_ders_mt.restype = ctypes.c_int64
            lib.ctmr_pack_ders_mt.argtypes = [
                ctypes.c_int64,
                u8p, i64p,
                ctypes.c_int64,
                u8p, i32p, u8p,
                ctypes.c_int64,
            ]
            lib.ctmr_pool_threads.restype = ctypes.c_int64
            lib.ctmr_pool_threads.argtypes = []
            lib.has_mt = True
        except AttributeError:
            lib.has_mt = False
        # SCT extraction (round 13; _v2 since round 24 — the RFC 6962
        # precert digest takes a per-lane issuer_key_hash input, so the
        # symbol is renamed: a stale pre-round-24 .so lacks it and
        # degrades to the python extractor instead of being called with
        # a mismatched signature). Same stale-library contract as
        # has_mt: callers check `has_sct`.
        try:
            lib.ctmr_extract_scts_v2.restype = None
            lib.ctmr_extract_scts_v2.argtypes = [
                ctypes.c_int64,
                u8p, ctypes.c_int64, i32p,
                u8p,
                u8p,
                u8p, u8p,
                i64p,
                u8p, u8p,
                u8p, u8p,
            ]
            lib.ctmr_extract_scts_v2_mt.restype = None
            lib.ctmr_extract_scts_v2_mt.argtypes = (
                lib.ctmr_extract_scts_v2.argtypes + [ctypes.c_int64]
            )
            lib.has_sct = True
        except AttributeError:
            lib.has_sct = False
        # Un-joined base64 columns (PR 26): the decoder reads each `str`
        # where it lies. The gather walks Python objects, so it is
        # called through a PyDLL handle (GIL held) and given the four
        # CPython functions it needs by address; the decode itself
        # stays on the GIL-releasing handle. Same stale-library
        # contract: callers check `has_strs` and join in Python.
        try:
            lib.ctmr_decode_entries_strs.restype = ctypes.c_int64
            lib.ctmr_decode_entries_strs.argtypes = [
                ctypes.c_int64,
                ctypes.c_void_p, i64p,
                ctypes.c_void_p, i64p,
                ctypes.c_int64,
                u8p, i32p,
                i64p, i32p,
                u8p, ctypes.c_int64,
                i64p, i32p,
                i32p,
                u8p, ctypes.c_int64,
                ctypes.c_int64, i64p,
            ]
            gather = ctypes.PyDLL(so).ctmr_gather_strs
            gather.restype = ctypes.c_int64
            gather.argtypes = [
                ctypes.py_object, ctypes.c_int64,
                ctypes.c_void_p, i64p,
            ] + [ctypes.c_void_p] * 4
            api = ctypes.pythonapi
            lib.gather_strs = gather
            lib.gather_pyapi = tuple(
                ctypes.cast(fn, ctypes.c_void_p)
                for fn in (api.PyList_GetItem, api.PyUnicode_AsUTF8AndSize,
                           api.PyUnicode_GetLength, api.PyErr_Clear))
            lib.has_strs = ctypes.sizeof(ctypes.c_void_p) == 8
        except (AttributeError, OSError):
            lib.has_strs = False
        # get-entries page scan (PR 30): offsets of the two base64
        # columns inside a response body; no Python object touched, so
        # it stays on this GIL-releasing handle. Same stale-library
        # contract: callers check `has_scan` and parse the page in
        # Python. The offsets are only of use to a decoder that reads
        # pointer columns, hence has_strs.
        try:
            lib.ctmr_scan_entries.restype = ctypes.c_int64
            lib.ctmr_scan_entries.argtypes = [
                ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
                i64p, i64p, i64p, i64p,
            ]
            lib.has_scan = lib.has_strs
        except AttributeError:
            lib.has_scan = False
        # A chunk of scanned pages decoded from a page table (PR 39):
        # the scan also leaves the page's four sizes, and the decoder
        # builds the per-entry pointer columns itself; no Python object
        # on either side, so both stay on this handle. Same
        # stale-library contract: callers check `has_pages` and build
        # the columns in NumPy. A table holds addresses as int64.
        try:
            lib.ctmr_scan_entries_stats.restype = ctypes.c_int64
            lib.ctmr_scan_entries_stats.argtypes = (
                lib.ctmr_scan_entries.argtypes + [i64p])
            lib.ctmr_decode_entries_pages.restype = ctypes.c_int64
            lib.ctmr_decode_entries_pages.argtypes = [
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_int64,
            ] + lib.ctmr_decode_entries_strs.argtypes[5:]
            lib.has_pages = lib.has_scan
        except AttributeError:
            lib.has_pages = False
        # Distinct byte windows of a batch (PR 37): reads the host rows
        # where they lie, no Python object, so it stays on this handle.
        # Same stale-library contract: `unique_windows` checks
        # `has_uniq` and its caller keeps the NumPy routine.
        try:
            lib.ctmr_unique_windows.restype = ctypes.c_int64
            lib.ctmr_unique_windows.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, ctypes.c_int64,
                ctypes.c_int64,
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, i64p,
            ]
            lib.has_uniq = True
        except AttributeError:
            lib.has_uniq = False
        # The dedup key's host fingerprint (PR 44): one SHA-256 block a
        # lane over four plain columns, no Python object, so it stays on
        # this handle. Same stale-library contract: `fingerprints`
        # checks `has_fp` and `core.packing.fingerprints_np` keeps the
        # NumPy routine.
        try:
            lib.ctmr_fingerprints.restype = ctypes.c_int64
            lib.ctmr_fingerprints.argtypes = [
                ctypes.c_int64, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_int64,
                ctypes.c_void_p, ctypes.c_void_p,
            ]
            lib.has_fp = True
        except AttributeError:
            lib.has_fp = False
        # Return stamps and the GIL probe's sleep (PR 38). The stamps
        # are read with the GIL held (the PyDLL handle, as the gather
        # is): a CDLL call would hand the GIL round once more. Same
        # stale-library contract: `note_return` and the tracer's probe
        # check `has_stamp` and say nothing.
        try:
            stamps = ctypes.PyDLL(so).ctmr_call_stamps
            stamps.restype = None
            stamps.argtypes = [i64p]
            lib.call_stamps = stamps
            lib.ctmr_sleep_stamp.restype = ctypes.c_int64
            lib.ctmr_sleep_stamp.argtypes = [ctypes.c_int64]
            lib.has_stamp = True
        except (AttributeError, OSError):
            lib.has_stamp = False
        _LIB = lib
        return _LIB


def available() -> bool:
    return load() is not None


def note_return(lib) -> None:
    """For the line after a call through the GIL-releasing handle, under
    an open span and with the tracer on: adds to that span's
    ``native_us`` the call's time inside the library (entry to return,
    GIL released, whatever threads it used) and to its ``gil_us`` the
    time from the library's return to this function's first
    instruction, which the thread spent taking the GIL back. Both add
    up over a span's calls. Nothing with a library that does not stamp
    its calls."""
    back = time.perf_counter_ns()  # CLOCK_MONOTONIC, as the stamps are
    if not getattr(lib, "has_stamp", False):
        return
    pair = (ctypes.c_int64 * 2)()
    lib.call_stamps(pair)
    trace.annotate_sum(native_us=(pair[1] - pair[0]) / 1e3,
                       gil_us=max(back - pair[1], 0) / 1e3)


def _lossless(a, dtype):
    """``a`` as a contiguous array of ``dtype``, or None where a value
    would not survive the conversion."""
    a = np.asarray(a)
    if a.dtype != dtype:
        b = a.astype(dtype)
        if not np.array_equal(a, b):
            return None
        a = b
    return np.ascontiguousarray(a)


def unique_windows(rows2d, row_sel, issuers, off, ln):
    """First lane of every distinct ``(issuer, length, window bytes)``
    among lanes ``i`` whose window ``rows2d[row_sel[i], off[i] : off[i]
    + ln[i]]`` lies wholly inside its row, and the lanes it left to the
    caller (a negative length, a window past the row's ends), both
    ascending: ``(first, rest)`` (``ctmr_unique_windows``). None where
    the library is unavailable or the input is not what it reads: rows
    that are not uint8 with each row's bytes contiguous, a row index
    outside the matrix, an index or offset no int32 / int64 holds."""
    lib = load()
    if lib is None or not getattr(lib, "has_uniq", False):
        return None
    if (not isinstance(rows2d, np.ndarray) or rows2d.ndim != 2
            or rows2d.dtype != np.uint8
            or (rows2d.shape[1] > 1 and rows2d.strides[1] != 1)):
        return None
    # Lanes as the function reads them: int64 rows, int32 the rest.
    lanes = [_lossless(row_sel, np.int64)] + [
        _lossless(a, np.int32) for a in (issuers, off, ln)]
    if any(a is None for a in lanes):
        return None
    n = int(lanes[0].shape[0])
    first = np.empty((n,), np.int64)
    rest = np.empty((n,), np.int64)
    n_rest = ctypes.c_int64(0)
    count = lib.ctmr_unique_windows(
        rows2d.ctypes.data, rows2d.shape[0], rows2d.strides[0],
        rows2d.shape[1],
        n, *(a.ctypes.data for a in lanes),
        first.ctypes.data, rest.ctypes.data, ctypes.byref(n_rest),
    )
    if trace.enabled():
        note_return(lib)
    if count < 0:
        return None
    # Copies, so that a few hundred indices do not keep two lane-sized
    # buffers alive.
    return first[:count].copy(), rest[: n_rest.value].copy()


def fingerprints(issuer_idx, exp_hour, serials, serial_len):
    """``uint32[n, 4]``: words 4..7 of the SHA-256 of every lane's
    fingerprint message (``ctmr_fingerprints``; the layout is
    ``core.packing``'s). The columns are converted as the NumPy routine
    it answers for converts them (issuer and hour wrap to ``uint32``,
    the length to ``int64``, the serial windows to ``uint8``), so any
    dtype or a list will do. None where the library is unavailable or
    the input is not what it reads: columns that are not ``n`` long, a
    window that is not ``core.packing.MAX_SERIAL_BYTES`` wide, a length
    outside the window."""
    lib = load()
    if lib is None or not getattr(lib, "has_fp", False):
        return None
    # astype copies: a column comes out dense whatever view came in.
    ii, eh = (np.asarray(a).astype(np.uint32)
              for a in (issuer_idx, exp_hour))
    slen = np.asarray(serial_len).astype(np.int64)
    win = np.asarray(serials, np.uint8)
    n = int(ii.shape[0]) if ii.ndim == 1 else -1
    if (eh.shape != (n,) or slen.shape != (n,)
            or win.shape != (n, FP_WINDOW_BYTES)):
        return None
    if win.strides[1] != 1:  # a row's bytes lie side by side
        win = np.ascontiguousarray(win)
    out = np.empty((n, 4), np.uint32)
    done = lib.ctmr_fingerprints(
        n, ii.ctypes.data, eh.ctypes.data, win.ctypes.data,
        win.strides[0], slen.ctypes.data, out.ctypes.data)
    if trace.enabled():
        note_return(lib)
    return out if done == n else None
