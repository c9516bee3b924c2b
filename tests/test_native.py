"""Native batch decoder: build, parity vs the Python leaf codec, and
throughput sanity. The native .so is a throughput optimization only —
`_decode_python` must produce byte-identical results, and the tests
run BOTH paths against the same wire data."""

import base64
import datetime

import numpy as np
import pytest

from ct_mapreduce_tpu.ingest import leaf as leaflib
from ct_mapreduce_tpu.native import available, leafpack

from tests import certgen

UTC = datetime.timezone.utc
FUTURE = datetime.datetime(2031, 6, 15, tzinfo=UTC)


def _wire_batch():
    issuer = certgen.make_cert(serial=1, issuer_cn="Native CA", is_ca=True,
                               not_after=FUTURE)
    lis, eds, expect = [], [], []
    for s in (10, 11, 12):
        leaf = certgen.make_cert(serial=s, issuer_cn="Native CA",
                                 is_ca=False, not_after=FUTURE)
        li = leaflib.encode_leaf_input(leaf, timestamp_ms=1700000000000 + s)
        ed = leaflib.encode_extra_data([issuer])
        lis.append(base64.b64encode(li).decode())
        eds.append(base64.b64encode(ed).decode())
        expect.append(leaf)
    # precert entry
    pre = certgen.make_cert(serial=99, issuer_cn="Native CA", is_ca=False,
                            not_after=FUTURE)
    li = leaflib.encode_leaf_input(b"\x00" * 12, timestamp_ms=5,
                                   entry_type=leaflib.PRECERT_ENTRY)
    ed = leaflib.encode_extra_data([issuer], entry_type=leaflib.PRECERT_ENTRY,
                                   pre_certificate=pre)
    lis.append(base64.b64encode(li).decode())
    eds.append(base64.b64encode(ed).decode())
    expect.append(pre)
    # garbage base64 + garbage leaf + no chain
    lis.append("!!!notb64!!!")
    eds.append("")
    expect.append(None)
    lis.append(base64.b64encode(b"\xff\xff\x00").decode())
    eds.append("")
    expect.append(None)
    leaf_nochain = certgen.make_cert(serial=13, issuer_cn="Native CA",
                                     is_ca=False, not_after=FUTURE)
    lis.append(base64.b64encode(
        leaflib.encode_leaf_input(leaf_nochain, timestamp_ms=7)).decode())
    eds.append("")
    expect.append(leaf_nochain)
    return lis, eds, expect, issuer


def _check(batch, expect, issuer):
    assert batch.status[0] == leafpack.OK
    for i, exp in enumerate(expect):
        if exp is None:
            assert batch.status[i] in (leafpack.BAD_B64, leafpack.BAD_LEAF,
                                       leafpack.UNSUPPORTED)
            assert batch.length[i] == 0
        else:
            got = batch.data[i, : batch.length[i]].tobytes()
            assert got == exp, f"lane {i} cert mismatch"
    # first three lanes: x509 with issuer
    for i in range(3):
        assert batch.entry_type[i] == leaflib.X509_ENTRY
        assert batch.issuers[i] == issuer
        assert batch.timestamp_ms[i] == 1700000000000 + (10 + i)
    # precert lane
    assert batch.entry_type[3] == leaflib.PRECERT_ENTRY
    assert batch.issuers[3] == issuer
    # no-chain lane: cert packed, NO_CHAIN status
    assert batch.status[6] == leafpack.NO_CHAIN
    assert batch.length[6] > 0
    assert batch.issuers[6] is None


def test_python_fallback_decode():
    lis, eds, expect, issuer = _wire_batch()
    batch = leafpack._decode_python(lis, eds, pad_len=2048)
    _check(batch, expect, issuer)


@pytest.mark.skipif(not available(), reason="no C++ compiler")
def test_native_decode_matches_python():
    lis, eds, expect, issuer = _wire_batch()
    nat = leafpack.decode_raw_batch(lis, eds, pad_len=2048)
    _check(nat, expect, issuer)
    py = leafpack._decode_python(lis, eds, pad_len=2048)
    np.testing.assert_array_equal(nat.data, py.data)
    np.testing.assert_array_equal(nat.length, py.length)
    np.testing.assert_array_equal(nat.timestamp_ms, py.timestamp_ms)
    np.testing.assert_array_equal(nat.entry_type, py.entry_type)
    np.testing.assert_array_equal(nat.status, py.status)
    assert nat.issuers == py.issuers


@pytest.mark.skipif(not available(), reason="no C++ compiler")
def test_native_python_agree_on_malformed_wire():
    """The tricky disagreement cases: over-padded base64, truncated
    extensions frame, truncated chain frame, truncated SECOND chain
    cert — native and Python must return identical statuses."""
    issuer = certgen.make_cert(serial=1, issuer_cn="Mal CA", is_ca=True,
                               not_after=FUTURE)
    leaf = certgen.make_cert(serial=5, issuer_cn="Mal CA", is_ca=False,
                             not_after=FUTURE)
    ok_ed = base64.b64encode(leaflib.encode_extra_data([issuer])).decode()

    li_full = leaflib.encode_leaf_input(leaf, timestamp_ms=1)
    cases = []
    # over-padded base64
    cases.append(("QUJD====", ok_ed))
    # extensions<2> frame missing entirely
    cases.append((base64.b64encode(li_full[:-2]).decode(), ok_ed))
    # extensions length pointing past the buffer
    trunc = li_full[:-2] + b"\x00\x10"
    cases.append((base64.b64encode(trunc).decode(), ok_ed))
    # chain frame length exceeding extra_data
    bad_frame = (len(issuer) + 100).to_bytes(3, "big") + issuer
    cases.append((base64.b64encode(li_full).decode(),
                  base64.b64encode(bad_frame).decode()))
    # second chain cert truncated
    inner = (len(issuer).to_bytes(3, "big") + issuer
             + (500).to_bytes(3, "big") + b"\x01\x02")
    bad2 = len(inner).to_bytes(3, "big") + inner
    cases.append((base64.b64encode(li_full).decode(),
                  base64.b64encode(bad2).decode()))
    # zero-length chain[0]
    empty0 = (3).to_bytes(3, "big") + (0).to_bytes(3, "big")
    cases.append((base64.b64encode(li_full).decode(),
                  base64.b64encode(empty0).decode()))

    lis = [c[0] for c in cases]
    eds = [c[1] for c in cases]
    nat = leafpack.decode_raw_batch(lis, eds, pad_len=2048)
    py = leafpack._decode_python(lis, eds, pad_len=2048)
    np.testing.assert_array_equal(nat.status, py.status)
    np.testing.assert_array_equal(nat.data, py.data)
    assert nat.issuers == py.issuers
    # and none of these were silently accepted as fully OK
    assert (nat.status != leafpack.OK).all()


@pytest.mark.skipif(not available(), reason="no C++ compiler")
def test_native_too_long_flagged():
    lis, eds, expect, issuer = _wire_batch()
    nat = leafpack.decode_raw_batch(lis[:1], eds[:1], pad_len=64)
    assert nat.status[0] == leafpack.TOO_LONG
    assert nat.length[0] == 0


def _timed(fn):
    import time

    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


@pytest.mark.skipif(not available(), reason="no C++ compiler")
def test_native_throughput_sanity():
    """The native path must beat per-entry Python decode comfortably."""
    lis, eds, _, _ = _wire_batch()
    lis, eds = lis[:3] * 700, eds[:3] * 700  # 2100 entries

    # Best-of-3 each: on this one-core host a single bad scheduling
    # slice under a loaded suite can flip a single-shot comparison.
    # Results are stashed by the timed runs — no extra decode passes.
    results = {}

    def run(name, fn):
        results[name] = fn()

    t_native = min(
        _timed(lambda: run(
            "nat", lambda: leafpack.decode_raw_batch(lis, eds, pad_len=2048)))
        for _ in range(3)
    )
    t_py = min(
        _timed(lambda: run(
            "py", lambda: leafpack._decode_python(lis, eds, pad_len=2048)))
        for _ in range(3)
    )
    np.testing.assert_array_equal(results["nat"].data, results["py"].data)
    assert t_native < t_py, (t_native, t_py)
    print(f"native {2100/t_native:,.0f}/s vs python {2100/t_py:,.0f}/s")


def test_decode_threaded_matches_single():
    """The thread-pool split (multi-core host path) must stitch results
    identical to the single-shot decode, including entry order, issuer
    bytes and status codes (mixed valid/garbage/no-chain wire)."""
    lis, eds, _expect, _issuer = _wire_batch()

    single = leafpack.decode_raw_batch(lis, eds, 2048, workers=1)
    multi = leafpack.decode_raw_batch(lis, eds, 2048, workers=3)
    np.testing.assert_array_equal(single.data, multi.data)
    np.testing.assert_array_equal(single.length, multi.length)
    np.testing.assert_array_equal(single.timestamp_ms, multi.timestamp_ms)
    np.testing.assert_array_equal(single.entry_type, multi.entry_type)
    np.testing.assert_array_equal(single.status, multi.status)
    assert single.issuers == multi.issuers


def test_wire_mutation_fuzz_native_python_agreement():
    """Seeded mutation fuzz over RFC 6962 wire bytes: the C++ decoder
    and the pure-Python codec must agree on status, packed cert bytes,
    timestamps and issuer DER for every mutant (the decode path feeds
    the device pipeline, so silent divergence corrupts identities)."""
    if not available():
        pytest.skip("native library unavailable")

    rng = np.random.default_rng(20260730)
    base_lis, base_eds, _expect, _issuer = _wire_batch()
    lis, eds = [], []
    for _ in range(250):
        j = int(rng.integers(len(base_lis)))
        li = base_lis[j]
        ed = base_eds[j]
        # Mutate the BASE64 TEXT half the time (exercises b64
        # validation parity) and the underlying bytes otherwise.
        if rng.random() < 0.5 and li:
            pos = int(rng.integers(len(li)))
            li = li[:pos] + chr(33 + int(rng.integers(90))) + li[pos + 1:]
        elif ed:
            raw = bytearray(base64.b64decode(ed))
            if raw:
                pos = int(rng.integers(len(raw)))
                raw[pos] ^= int(rng.integers(1, 256))
                ed = base64.b64encode(bytes(raw)).decode()
        lis.append(li)
        eds.append(ed)

    nat = leafpack.decode_raw_batch(lis, eds, 2048, workers=1)
    py = leafpack._decode_python(lis, eds, 2048)
    np.testing.assert_array_equal(nat.status, py.status)
    np.testing.assert_array_equal(nat.length, py.length)
    np.testing.assert_array_equal(nat.data, py.data)
    np.testing.assert_array_equal(nat.timestamp_ms, py.timestamp_ms)
    np.testing.assert_array_equal(nat.entry_type, py.entry_type)
    assert nat.issuers == py.issuers


# -- base64 columns read in place, and the join as the fallback (PR 26) ----


class _Str(str):
    """A ``str`` subclass: not compact storage, still ASCII."""


def _column_case(case, lis, eds):
    if case == "str_subclass":
        return [_Str(s) for s in lis], [_Str(s) for s in eds]
    if case == "tuple_of_str":
        return tuple(lis), tuple(eds)
    if case == "bytes":
        return [s.encode() for s in lis], [s.encode() for s in eds]
    if case == "mixed_str_bytes":
        return ([s.encode() if i % 2 else s for i, s in enumerate(lis)],
                [s if i % 2 else s.encode() for i, s in enumerate(eds)])
    if case == "bytes_high_bit":  # not base64, not ASCII: a status
        return ([s.encode() for s in lis[:-1]] + [b"\xff\xfe" + b"A" * 6],
                [s.encode() for s in eds])
    return lis, eds  # "str", "stale_library"


@pytest.mark.skipif(not available(), reason="no C++ compiler")
@pytest.mark.parametrize("threads", [1, 3])
@pytest.mark.parametrize("case,joined", [
    ("str", 0), ("str_subclass", 0), ("tuple_of_str", 0), ("bytes", 1),
    ("mixed_str_bytes", 1), ("bytes_high_bit", 1), ("stale_library", 1)])
def test_b64_columns_in_place_or_joined_same_batch(
        case, joined, threads, monkeypatch):
    """Columns the decoder can read in place (ASCII ``str`` items) are
    not joined; every other input takes the join as before. Both give
    the batch the pure-Python lane gives."""
    from ct_mapreduce_tpu.native import load
    from ct_mapreduce_tpu.telemetry import trace

    lis, eds, _expect, _issuer = _wire_batch()
    lis, eds = _column_case(case, lis * 5, eds * 5)
    if case == "stale_library":  # a prebuilt .so without the entry point
        monkeypatch.setattr(load(), "has_strs", False)
    want = leafpack._decode_python(list(lis), list(eds), 2048)
    trace.enable(ring_size=64)
    try:
        got = leafpack.decode_raw_batch(lis, eds, 2048, threads=threads)
        spans = [e for e in trace.snapshot_events()
                 if e["name"] == "decode.concat_b64"]
    finally:
        trace.disable()
    for fld in ("data", "length", "timestamp_ms", "entry_type", "status",
                "issuer_group"):
        np.testing.assert_array_equal(
            getattr(got, fld), getattr(want, fld), err_msg=f"{case}: {fld}")
    assert got.group_issuers == want.group_issuers
    assert got.issuers == want.issuers
    assert spans[-1]["args"]["joined"] == joined
    assert spans[-1]["args"]["bytes"] == sum(map(len, lis)) + sum(map(len, eds))
    if case == "bytes_high_bit":
        assert got.status[-1] == leafpack.BAD_B64


@pytest.mark.skipif(not available(), reason="no C++ compiler")
@pytest.mark.parametrize("threads", [1, 3])
@pytest.mark.parametrize("stamps", [True, False], ids=["stamped", "stale"])
def test_the_native_call_says_when_it_returned(stamps, threads, monkeypatch):
    """``decode.native_call`` carries ``native_us`` (inside the library,
    GIL released) and ``gil_us`` (its return to the next Python
    instruction), both inside the span; a prebuilt .so without the
    stamps leaves both out and decodes the same batch."""
    from ct_mapreduce_tpu.native import load
    from ct_mapreduce_tpu.telemetry import trace

    lis, eds, _expect, _issuer = _wire_batch()
    lis, eds = lis * 40, eds * 40
    if not stamps:
        monkeypatch.setattr(load(), "has_stamp", False)
    want = leafpack._decode_python(list(lis), list(eds), 2048)
    tracer = trace._tracer = trace.SpanTracer(ring_size=64)
    try:
        got = leafpack.decode_raw_batch(lis, eds, 2048, threads=threads)
    finally:
        trace._tracer = None
    for fld in ("data", "length", "timestamp_ms", "entry_type", "status",
                "issuer_group"):
        np.testing.assert_array_equal(getattr(got, fld), getattr(want, fld))
    (call,) = [e for e in tracer.events() if e["name"] == "decode.native_call"]
    others = [e for e in tracer.events() if e.get("ph") == "X"
              and e["name"] != "decode.native_call"]
    assert not any("gil_us" in e.get("args", {}) for e in others)
    if not stamps:
        assert set(call["args"]) == {"threads", "pad"}
        return
    args = call["args"]
    assert args["native_us"] > 0 and args["gil_us"] >= 0
    assert args["native_us"] + args["gil_us"] <= call["dur"]
    # With the tracer off the call is not followed up at all.
    calls = []
    monkeypatch.setattr(leafpack, "note_return", calls.append)
    leafpack.decode_raw_batch(lis, eds, 2048, threads=threads)
    assert calls == []


def test_the_stamp_follows_the_sleep_it_timed():
    """``ctmr_sleep_stamp`` stamps the clock ``monotonic_ns`` reads, as
    it wakes: after the sleep it was asked for, before Python runs
    again."""
    import time

    from ct_mapreduce_tpu.native import load

    lib = load()
    if lib is None or not lib.has_stamp:
        pytest.skip("no native library that stamps")
    before = time.monotonic_ns()
    woke = lib.ctmr_sleep_stamp(2_000_000)
    after = time.monotonic_ns()
    assert before + 2_000_000 <= woke <= after


@pytest.mark.skipif(not available(), reason="no C++ compiler")
@pytest.mark.parametrize("stale", [False, True], ids=["in_place", "stale"])
@pytest.mark.parametrize("case,exc", [
    ("non_ascii_leaf_input", UnicodeEncodeError),
    ("non_ascii_extra_data", UnicodeEncodeError),
    ("none_item", TypeError),
    ("int_item", TypeError)])
def test_b64_columns_bad_items_raise_as_the_join_does(
        case, exc, stale, monkeypatch):
    """An item the fast path cannot take is no new failure mode: the
    call raises what the Python join has always raised for it, with or
    without the in-place entry point, and leaves no error behind."""
    from ct_mapreduce_tpu.native import load

    lis, eds, _expect, _issuer = _wire_batch()
    if case == "non_ascii_leaf_input":
        lis[2] = lis[2][:9] + "é" + lis[2][10:]
    elif case == "non_ascii_extra_data":
        eds[0] = "✓" + eds[0][1:]
    elif case == "none_item":
        eds[1] = None
    else:
        lis[-1] = 7
    if stale:
        monkeypatch.setattr(load(), "has_strs", False)
    with pytest.raises(exc):
        leafpack.decode_raw_batch(lis, eds, 2048, threads=2)
    # and the next batch decodes
    lis, eds, expect, issuer = _wire_batch()
    _check(leafpack.decode_raw_batch(lis, eds, 2048), expect, issuer)
