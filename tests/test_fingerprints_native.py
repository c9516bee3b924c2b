"""The host fingerprint as one native call (``ctmr_fingerprints``, PR
44): ``core.packing.fingerprints_np`` answers to the bit what the NumPy
routine it replaced answers (``packing._fingerprints_numpy``, kept as
the one fallback and as an oracle here), what ``hashlib.sha256`` makes
of the 9-byte header and the serial put together here, and what the
device's ``ops.pipeline.fingerprints`` makes of the same batch; over
every serial length, both ends of the issuer and hour ranges, and the
dtypes and layouts its callers pass. Every call says how many lanes it
had and how many took the NumPy routine.
"""

import ctypes
import hashlib
import struct

import numpy as np
import pytest

from ct_mapreduce_tpu import native
from ct_mapreduce_tpu.core import packing
from ct_mapreduce_tpu.telemetry import metrics, trace

W = packing.MAX_SERIAL_BYTES
HOURS = (packing.DEFAULT_BASE_HOUR,
         packing.DEFAULT_BASE_HOUR + packing.META_HOUR_SPAN - 1)
ISSUERS = (0, packing.MAX_ISSUERS - 1)

needs_native = pytest.mark.skipif(
    not getattr(native.load(), "has_fp", False),
    reason="native library unavailable")


@pytest.fixture(autouse=True)
def clean_telemetry():
    trace.disable()
    metrics.set_sink(metrics.InMemSink())
    yield
    trace.disable()
    metrics.set_sink(metrics.InMemSink())


def counters() -> dict:
    got = metrics.get_sink().snapshot()["counters"]
    return {k: got.get("fp." + k) for k in ("lanes", "fallback_lanes")}


def columns(n: int, seed: int = 7):
    """``n`` lanes: every serial length 0..46 in turn, the issuer and the
    hour at the ends of their ranges on the first lanes and anywhere
    after, the window zero past the serial."""
    rng = np.random.default_rng(seed)
    slen = np.arange(n, dtype=np.int64) % (W + 1)
    ii = rng.integers(0, packing.MAX_ISSUERS, n)
    eh = rng.integers(HOURS[0], HOURS[1] + 1, n)
    ii[: len(ISSUERS)] = ISSUERS[:n]
    eh[: len(HOURS)] = HOURS[:n]
    ii[len(ISSUERS): 2 * len(ISSUERS)] = ISSUERS[::-1][: max(n - 2, 0)]
    ser = rng.integers(0, 256, (n, W)).astype(np.uint8)
    ser[np.arange(W)[None, :] >= slen[:, None]] = 0
    return ii, eh, ser, slen


def by_hashlib(ii, eh, ser, slen) -> np.ndarray:
    """Words 4..7 of SHA-256(expHour BE | issuerIdx BE | len | serial),
    from nothing of the program but the layout its docstring states."""
    out = np.zeros((len(ii), 4), np.uint32)
    for k in range(len(ii)):
        serial = bytes(bytearray(int(b) for b in ser[k][: int(slen[k])]))
        msg = struct.pack(">IIB", int(eh[k]), int(ii[k]), len(serial)) + serial
        out[k] = struct.unpack(">8I", hashlib.sha256(msg).digest())[4:]
    return out


@needs_native
@pytest.mark.parametrize("n", [0, 1, 2, 17, 4096])
def test_native_is_the_numpy_routine_and_hashlib_to_the_bit(n):
    cols = columns(n)
    got = packing.fingerprints_np(*cols)
    assert got.dtype == np.uint32 and got.shape == (n, 4)
    assert counters() == {"lanes": n, "fallback_lanes": 0}
    assert np.array_equal(got, native.fingerprints(*cols))
    assert np.array_equal(got, packing._fingerprints_numpy(*cols))
    assert np.array_equal(got, by_hashlib(*cols))
    assert counters() == {"lanes": n, "fallback_lanes": 0}  # oracles say nothing


@needs_native
@pytest.mark.parametrize("slen", range(W + 1))
def test_every_serial_length_at_both_ends_of_issuer_and_hour(slen):
    """Four lanes a length: issuer 0 and the last, the meta span's first
    hour and its last; at 46 bytes the 0x80 is the block's 56th byte."""
    ii = np.array([i for i in ISSUERS for _ in HOURS])
    eh = np.array([h for _ in ISSUERS for h in HOURS])
    ser = np.zeros((4, W), np.uint8)
    ser[:, :slen] = (np.arange(slen) * 5 + 0x81) % 256
    lens = np.full((4,), slen)
    got = packing.fingerprints_np(ii, eh, ser, lens)
    assert np.array_equal(got, by_hashlib(ii, eh, ser, lens))
    assert np.array_equal(got, packing._fingerprints_numpy(ii, eh, ser, lens))
    assert tuple(got[3]) == packing.fingerprint_host(
        ISSUERS[1], HOURS[1], bytes(ser[3, :slen]))
    assert len({tuple(r) for r in got}) == 4
    assert counters() == {"lanes": 4, "fallback_lanes": 0}


def as_int64(ii, eh, ser, slen):
    return ii.astype(np.int64), eh.astype(np.int64), ser, slen.astype(np.int64)


def as_int32(ii, eh, ser, slen):
    return ii.astype(np.int32), eh.astype(np.int32), ser, slen.astype(np.int32)


def as_lists(ii, eh, ser, slen):
    return ii.tolist(), eh.tolist(), ser.tolist(), slen.tolist()


def as_strided(ii, eh, ser, slen):
    """Every column a view of every other element of a wider array, the
    windows a slice of wider rows: nothing contiguous."""
    def every_other(a):
        wide = np.zeros((2 * len(a),), a.dtype)
        wide[::2] = a
        return wide[::2]
    rows = np.full((2 * len(ii), W + 18), 0xEE, np.uint8)
    rows[::2, 9:9 + W] = ser
    return (every_other(ii), every_other(eh), rows[::2, 9:9 + W],
            every_other(slen))


def as_fortran_windows(ii, eh, ser, slen):
    """A column-major window matrix: a row's bytes do not lie side by
    side, so the wrapper has to copy before the call."""
    return ii, eh, np.asfortranarray(ser), slen


def as_reversed(ii, eh, ser, slen):
    return ii[::-1], eh[::-1], ser[::-1], slen[::-1]


def as_uint_columns(ii, eh, ser, slen):
    return (ii.astype(np.uint16), eh.astype(np.uint32), ser,
            slen.astype(np.uint8))


@needs_native
@pytest.mark.parametrize("shape", [
    as_int64, as_int32, as_lists, as_strided, as_fortran_windows,
    as_reversed, as_uint_columns], ids=lambda f: f.__name__)
def test_dtypes_and_layouts_the_callers_pass(shape):
    """``TableView.lookup`` passes int64 columns, the restore int32, the
    filter builds uint8 lengths, a test lists: all the same words, all
    through the native call."""
    cols = columns(96, seed=11)
    given = shape(*cols)
    want = by_hashlib(*cols)
    if shape is as_reversed:
        want = want[::-1]
    got = packing.fingerprints_np(*given)
    assert np.array_equal(got, want)
    assert np.array_equal(got, packing._fingerprints_numpy(*given))
    assert counters() == {"lanes": 96, "fallback_lanes": 0}


@needs_native
def test_hours_and_issuers_wrap_as_the_numpy_routine_wraps_them():
    """Values no caller sends but the routine accepts: a negative hour
    and an issuer past 32 bits take the low 32 bits, as ``astype``
    does."""
    ii = np.array([-1, 1 << 32, 5], np.int64)
    eh = np.array([-7, (1 << 33) + 9, 400_123], np.int64)
    ser = np.zeros((3, W), np.uint8)
    ser[:, 0] = (1, 2, 3)
    slen = np.ones((3,), np.int64)
    got = packing.fingerprints_np(ii, eh, ser, slen)
    assert np.array_equal(got, packing._fingerprints_numpy(ii, eh, ser, slen))
    assert np.array_equal(got, by_hashlib(ii & 0xFFFFFFFF, eh & 0xFFFFFFFF,
                                          ser, slen))
    assert counters() == {"lanes": 3, "fallback_lanes": 0}


@needs_native
def test_bytes_past_the_serial_go_into_the_block_as_they_do_in_numpy():
    """The contract leaves them zero; a caller that does not gets the
    NumPy routine's answer and not another."""
    ii, eh, ser, slen = columns(64, seed=3)
    ser[:, -1] = 0xAB  # lanes shorter than 46 now carry a stray byte
    got = packing.fingerprints_np(ii, eh, ser, slen)
    assert np.array_equal(got, packing._fingerprints_numpy(ii, eh, ser, slen))
    full = slen == W
    assert np.array_equal(got[full], by_hashlib(ii, eh, ser, slen)[full])
    assert not np.array_equal(got[~full], by_hashlib(ii, eh, ser, slen)[~full])


@needs_native
@pytest.mark.parametrize("what", ["length_past_window", "negative_length",
                                  "narrow_window", "short_column"])
def test_input_the_native_call_does_not_read_takes_the_numpy_routine(what):
    """The native wrapper answers None and says nothing of its own; the
    NumPy routine then does whatever it did before (an answer for a
    length outside the window, its own error for a ragged input), and
    the lanes count as fallen back."""
    ii, eh, ser, slen = columns(8)
    if what == "length_past_window":
        slen = slen.copy()
        slen[3] = W + 1
    elif what == "negative_length":
        slen = slen.copy()
        slen[5] = -2
    elif what == "narrow_window":
        ser = ser[:, :40]
    else:
        eh = eh[:7]
    assert native.fingerprints(ii, eh, ser, slen) is None
    if what in ("narrow_window", "short_column"):
        with pytest.raises(ValueError):
            packing.fingerprints_np(ii, eh, ser, slen)
        return
    got = packing.fingerprints_np(ii, eh, ser, slen)
    assert np.array_equal(got, packing._fingerprints_numpy(ii, eh, ser, slen))
    assert counters() == {"lanes": 8, "fallback_lanes": 8}


@pytest.mark.parametrize("n", [0, 1, 2, 17, 4096])
@pytest.mark.parametrize("gone", ["no_library", "older_library"])
def test_without_the_library_the_same_words_and_every_lane_counted(
        monkeypatch, n, gone):
    cols = columns(n, seed=5)
    with_it = packing.fingerprints_np(*cols)
    metrics.set_sink(metrics.InMemSink())
    if gone == "no_library":
        monkeypatch.setattr(native, "load", lambda: None)
    elif native.load() is not None:
        monkeypatch.setattr(native.load(), "has_fp", False)
    assert native.fingerprints(*cols) is None
    without = packing.fingerprints_np(*cols)
    assert without.dtype == with_it.dtype and without.shape == with_it.shape
    assert np.array_equal(without, with_it)
    assert np.array_equal(without, by_hashlib(*cols))
    assert counters() == {"lanes": n, "fallback_lanes": n}


@needs_native
def test_the_device_computes_the_same_words_on_one_batch():
    import jax.numpy as jnp

    from ct_mapreduce_tpu.ops import pipeline

    ii, eh, ser, slen = columns(128, seed=13)
    dev = np.asarray(pipeline.fingerprints(
        jnp.asarray(ii, jnp.int32), jnp.asarray(eh, jnp.int32),
        jnp.asarray(ser), jnp.asarray(slen, jnp.int32)))
    assert np.array_equal(packing.fingerprints_np(ii, eh, ser, slen), dev)
    assert counters() == {"lanes": 128, "fallback_lanes": 0}


@needs_native
def test_the_symbol_is_on_the_handle_that_releases_the_gil():
    """``ctypes.CDLL`` drops the GIL round a foreign call, ``PyDLL`` keeps
    it (``ctmr_gather_strs``, ``ctmr_call_stamps``): the fingerprint
    touches no Python object and is bound on the first."""
    lib = native.load()
    assert isinstance(lib, ctypes.CDLL) and not isinstance(lib, ctypes.PyDLL)
    fn = lib.ctmr_fingerprints
    assert not fn._flags_ & ctypes._FUNCFLAG_PYTHONAPI
    assert lib.call_stamps._flags_ & ctypes._FUNCFLAG_PYTHONAPI
    assert native.FP_WINDOW_BYTES == packing.MAX_SERIAL_BYTES
    assert packing.FP_MSG_BYTES == 9 + W <= 55  # one block after padding


@needs_native
def test_an_open_span_says_what_the_call_cost(monkeypatch):
    """Under a span and a tracer, as ``serve.lookup`` is: the native
    call's own stamps land on the span (``native_us`` / ``gil_us``), as
    for the other calls through this handle; without a tracer nothing
    is read."""
    if not native.load().has_stamp:
        pytest.skip("a library that does not stamp")
    tracer = trace.SpanTracer()
    monkeypatch.setattr(trace, "_tracer", tracer)
    cols = columns(4096)
    with trace.span("serve.lookup", cat="serve"):
        packing.fingerprints_np(*cols)
    args = next(e for e in tracer.events()
                if e["name"] == "serve.lookup")["args"]
    assert args["native_us"] > 0.0 and args["gil_us"] >= 0.0
