"""The fold's distinct windows (``ctmr_unique_windows``, PR 37): the
native pass reaches exactly the ``(issuer, raw bytes)`` pairs that the
NumPy routine it replaced reaches (``aggregator._rep_windows_numpy``,
kept as the one fallback and as the oracle here), over the corners the
routine has: empty and negative lengths, windows at and past a row's
end, rows that are views, a table that has to grow; and it does so
without a window's worth of temporaries a lane.
"""

import tracemalloc

import numpy as np
import pytest

from ct_mapreduce_tpu import native
from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
from ct_mapreduce_tpu.telemetry import metrics, trace

from certgen import make_cert

pytestmark = pytest.mark.skipif(
    not getattr(native.load(), "has_uniq", False),
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
    return {k: got.get("fold.meta_" + k)
            for k in ("lanes", "fallback_lanes", "distinct")}


def der_name(cn: str) -> bytes:
    """Name ::= SEQUENCE { SET { SEQUENCE { OID 2.5.4.3, UTF8 cn } } }."""
    atv = b"\x06\x03\x55\x04\x03" + b"\x0c" + bytes([len(cn)]) + cn.encode()
    atv = b"\x30" + bytes([len(atv)]) + atv
    rdn = b"\x31" + bytes([len(atv)]) + atv
    return b"\x30" + bytes([len(rdn)]) + rdn


def der_crldp(url: str) -> bytes:
    """SEQUENCE OF DistributionPoint { [0] { [0] { [6] url } } }."""
    uri = b"\x86" + bytes([len(url)]) + url.encode()
    full = b"\xa0" + bytes([len(uri)]) + uri
    point = b"\xa0" + bytes([len(full)]) + full
    dp = b"\x30" + bytes([len(point)]) + point
    return b"\x30" + bytes([len(dp)]) + dp


class Batch:
    """``n`` lanes of ``width``-byte rows, each holding one of
    ``names`` and one of ``dps`` (by the lane's issuer, plus ``kinds``
    variants an issuer) at an offset of its own."""

    def __init__(self, seed: int, n: int, width: int, issuers: int,
                 kinds: int = 1, name_len: int = 64, dp_len: int = 45):
        rng = np.random.default_rng(seed)
        self.rows = rng.integers(0, 256, (n, width), dtype=np.uint8)
        self.issuers = rng.integers(0, issuers, n).astype(np.int32)
        kind = rng.integers(0, kinds, n)
        pool = issuers * kinds
        names = rng.integers(0, 256, (pool, name_len), dtype=np.uint8)
        dps = rng.integers(0, 256, (pool, dp_len), dtype=np.uint8)
        # A few that parse, so dn_sets / crl_sets are not empty.
        for p in range(min(pool, 8)):
            nm = der_name(f"Issuer {p}".ljust(name_len - 11, "x"))
            names[p, :] = np.frombuffer(nm, np.uint8)[:name_len]
            dp = der_crldp(f"http://crl{p}.example/".ljust(dp_len - 10, "y"))
            dps[p, :] = np.frombuffer(dp, np.uint8)[:dp_len]
        which = self.issuers * kinds + kind
        half = width // 2
        self.in_off = rng.integers(0, half - name_len, n).astype(np.int32)
        self.dp_off = rng.integers(half, width - dp_len, n).astype(np.int32)
        self.in_len = np.full((n,), name_len, np.int32)
        self.dp_len = np.full((n,), dp_len, np.int32)
        lanes = np.arange(n)[:, None]
        self.rows[lanes, self.in_off[:, None] + np.arange(name_len)] \
            = names[which]
        self.rows[lanes, self.dp_off[:, None] + np.arange(dp_len)] \
            = dps[which]
        self.sel = np.flatnonzero(rng.random(n) < 0.9).astype(np.int64)

    def args(self):
        s = self.sel
        return (self.rows, s, self.issuers[s], self.dp_off[s],
                self.dp_len[s], self.in_off[s], self.in_len[s])


def fold(args, library: bool, monkeypatch) -> tuple:
    """What one ``_accumulate_metadata_lanes`` leaves behind, with the
    library or made to do without, and the fold's three counters."""
    agg = TpuAggregator.__new__(TpuAggregator)
    agg._dn_raw_seen, agg._crl_raw_seen = set(), set()
    agg.dn_sets, agg.crl_sets = {}, {}
    metrics.set_sink(metrics.InMemSink())
    with monkeypatch.context() as m:
        if not library:
            m.setattr(native, "load", lambda: None)
        agg._accumulate_metadata_lanes(*args)
    return ((agg._dn_raw_seen, agg._crl_raw_seen, agg.dn_sets,
             agg.crl_sets), counters())


# A shape edits the batch in place and returns how many of the selected
# lanes it sends to the NumPy routine.


def one_issuer(b):
    return 0


def table_growth(b):
    """Thousands of distinct windows: the table doubles a few times."""
    return 0


def same_place_other_bytes(b):
    """Two lanes, one issuer, offset and length, different bytes: two
    windows. And two rows with the same bytes at different offsets:
    one."""
    s = b.sel
    a, c, d = s[0], s[1], s[2]
    b.issuers[[a, c, d]] = 0
    b.in_off[[a, c]] = 10
    b.in_off[d] = 200
    b.rows[a, 10:74] = 7
    b.rows[c, 10:74] = 7
    b.rows[c, 73] = 8
    b.rows[d, 200:264] = 7
    return 0


def no_crldp(b):
    """``dp_len`` 0 (the extension absent, at any offset) and negative:
    skipped, whoever finds them."""
    s = b.sel
    b.dp_len[s[::3]] = 0
    b.dp_off[s[::6]] = -5
    b.dp_len[s[1::7]] = -3
    return int(np.count_nonzero(b.dp_len[s] < 0))


def empty_names(b):
    """A name of no bytes is a window too: one an issuer, as before."""
    s = b.sel
    b.in_len[s[::4]] = 0
    b.in_off[s[::8]] = b.rows.shape[1] + 9
    return 0


def ends_on_last_byte(b):
    """A window that ends on its row's last byte lies inside it."""
    s = b.sel
    b.dp_off[s[::5]] = b.rows.shape[1] - b.dp_len[s[::5]]
    b.rows[s[::5], -1] = 0x5A
    return 0


def runs_past_the_row(b):
    """Windows that start before the row or end after it: the NumPy
    routine's, with its clipping; the others stay native."""
    s = b.sel
    b.dp_off[s[::9]] = b.rows.shape[1] - 20
    b.in_off[s[1::11]] = -3
    b.in_off[s[2::13]] = b.rows.shape[1] - 1
    out = np.zeros(b.rows.shape[0], bool)
    out[s[::9]] = out[s[1::11]] = out[s[2::13]] = True
    return int(out[s].sum())


def row_view(b):
    """Every other row of a wider matrix, its right half cut off: rows
    far apart, each row's bytes still contiguous."""
    wide = np.zeros((b.rows.shape[0] * 2, b.rows.shape[1] + 512), np.uint8)
    view = wide[1::2, : b.rows.shape[1]]
    view[:] = b.rows
    b.rows = view
    assert not view.flags.c_contiguous and view.strides[1] == 1
    return 0


def strided_bytes(b):
    """Every other byte of a wider matrix: no row's bytes are
    contiguous, so every lane is the NumPy routine's."""
    wide = np.zeros((b.rows.shape[0], b.rows.shape[1] * 2), np.uint8)
    view = wide[:, ::2]
    view[:] = b.rows
    b.rows = view
    return int(b.sel.size)


def empty_selection(b):
    b.sel = b.sel[:0]
    return 0


CASES = [
    (one_issuer, dict(n=4096, width=2048, issuers=1)),
    (table_growth, dict(n=8192, width=1024, issuers=64, kinds=80)),
    (same_place_other_bytes, dict(n=512, width=1024, issuers=3)),
    (no_crldp, dict(n=2048, width=1024, issuers=5)),
    (empty_names, dict(n=2048, width=1024, issuers=5)),
    (ends_on_last_byte, dict(n=2048, width=1024, issuers=7)),
    (ends_on_last_byte, dict(n=2048, width=2048, issuers=7)),
    (runs_past_the_row, dict(n=2048, width=1024, issuers=7, kinds=3)),
    (runs_past_the_row, dict(n=2048, width=2048, issuers=7, kinds=3)),
    (row_view, dict(n=1024, width=1024, issuers=16, kinds=2)),
    (strided_bytes, dict(n=1024, width=1024, issuers=16, kinds=2)),
    (empty_selection, dict(n=64, width=1024, issuers=2)),
]


@pytest.mark.parametrize("seed", [1, 2147483659])
@pytest.mark.parametrize(
    "shape,kw", CASES,
    ids=[f"{c.__name__}-{kw['width']}" for c, kw in CASES])
def test_native_pass_reaches_what_the_numpy_routine_reaches(
        shape, kw, seed, monkeypatch):
    batch = Batch(seed, **kw)
    fallback_lanes = shape(batch)
    args = batch.args()
    want, without = fold(args, False, monkeypatch)
    got, with_lib = fold(args, True, monkeypatch)
    assert got == want
    n = int(batch.sel.size)
    if n == 0:  # nothing handed to the fold, nothing counted
        assert set(with_lib.values()) == {None}
        return
    assert got[0]
    assert with_lib["lanes"] == without["lanes"] == n
    assert without["fallback_lanes"] == n  # the fallback alone
    assert with_lib["fallback_lanes"] == fallback_lanes
    # Distinct raw pairs seen for the first time = representatives,
    # less the CRLDP classes the loop skips (dp_len <= 0).
    assert with_lib["distinct"] >= len(got[0]) + len(got[1])


def test_table_growth_case_has_thousands_of_windows(monkeypatch):
    batch = Batch(3, n=8192, width=1024, issuers=64, kinds=80)
    got, count = fold(batch.args(), True, monkeypatch)
    assert len(got[0]) > 3000 and len(got[1]) > 3000
    assert count["distinct"] == len(got[0]) + len(got[1])
    assert count["fallback_lanes"] == 0


def test_first_lane_of_each_class_ascending():
    """The function itself: first lanes ascending, the rest ascending,
    and None (the caller's routine) for what it does not read."""
    rows = np.zeros((4, 16), np.uint8)
    rows[1, 4:8] = 9
    rows[3, 0:4] = 9
    sel = np.array([0, 1, 2, 3, 1], np.int64)
    iss = np.array([0, 0, 1, 0, 0], np.int32)
    off = np.array([0, 4, 0, 0, 14], np.int32)
    ln = np.array([4, 4, 4, 4, 4], np.int32)
    first, rest = native.unique_windows(rows, sel, iss, off, ln)
    assert first.tolist() == [0, 1, 2] and rest.tolist() == [4]
    assert native.unique_windows(rows, sel + 1, iss, off, ln) is None
    assert native.unique_windows(rows, -sel, iss, off, ln) is None
    assert native.unique_windows(
        rows.astype(np.int16), sel, iss, off, ln) is None
    assert native.unique_windows(
        rows, sel, iss, off.astype(np.int64) + (1 << 40), ln) is None
    # Wider integers that hold the same values are the same question.
    first2, rest2 = native.unique_windows(
        rows, sel.astype(np.int32), iss.astype(np.int64),
        off.astype(np.int64), ln.astype(np.int16))
    assert first2.tolist() == [0, 1, 2] and rest2.tolist() == [4]


def test_a_prebuilt_library_without_the_entry_point(monkeypatch):
    """The stale-library contract of the file's other entry points: a
    library from before PR 37 loads, and the fold keeps the routine."""
    monkeypatch.setattr(native.load(), "has_uniq", False)
    batch = Batch(5, n=256, width=1024, issuers=3)
    assert native.unique_windows(*batch.args()[:3], batch.in_off[batch.sel],
                                 batch.in_len[batch.sel]) is None
    _got, count = fold(batch.args(), True, monkeypatch)
    assert count["fallback_lanes"] == count["lanes"] == batch.sel.size


def test_a_fold_makes_no_window_a_lane(monkeypatch):
    """The point of the change, without a clock: one metadata fold of a
    whole batch of the benchmark's shapes (65,536 rows of 2,048 B, 64 B
    names, 45 B CRLDPs) stays under 4 MB of temporaries (the NumPy
    routine: 36 MB, two 63,570 x 64 index matrices and the gathered
    windows)."""
    batch = Batch(7, n=65536, width=2048, issuers=16)
    args = batch.args()
    fold(args, True, monkeypatch)  # the library is loaded
    tracemalloc.start()
    try:
        got, count = fold(args, True, monkeypatch)
        _size, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    assert count["fallback_lanes"] == 0 and count["distinct"] == 32
    assert len(got[0]) == len(got[1]) == 16
    assert peak < 4 << 20, peak


def test_the_span_says_what_the_counters_say(monkeypatch):
    trace.enable()
    batch = Batch(11, n=512, width=1024, issuers=4)
    runs_past_the_row(batch)
    _got, count = fold(batch.args(), True, monkeypatch)
    (span,) = [e for e in trace.snapshot_events()
               if e["name"] == "fold.metadata"]
    assert span["args"]["lanes"] == count["lanes"]
    assert span["args"]["fallback_lanes"] == count["fallback_lanes"] > 0
    assert span["args"]["distinct"] == count["distinct"]
    # Both native passes of the fold (names, CRLDP) add to the span's
    # time inside the library and to what the GIL cost on their return.
    assert span["args"]["native_us"] > 0 and span["args"]["gil_us"] >= 0
    assert span["args"]["native_us"] + span["args"]["gil_us"] <= span["dur"]


def test_same_entries_with_the_library_and_without(monkeypatch):
    """End to end: the same packed entries through ``ingest_packed``,
    twice (the second time every lane is known), give equal
    ``dn_sets``, ``crl_sets``, ``metrics`` and ``issuer_totals``."""
    import datetime

    from ct_mapreduce_tpu.core import packing

    now = datetime.datetime(2024, 6, 1, tzinfo=datetime.timezone.utc)
    cas = [make_cert(issuer_cn=f"Window CA {i}", key_seed=i)
           for i in range(3)]
    crls = [("http://crl.example.com/a.crl",),
            ("http://crl.example.com/a.crl", "https://crl.example.com/b.crl"),
            ()]
    leaves = []
    for s in range(30):
        i = s % 3
        leaves.append((make_cert(
            serial=9000 + s, issuer_cn=f"Window CA {i}", is_ca=False,
            subject_cn=f"w{s}.example.com",
            crl_dps=crls[(s // 3) % 3]), i))

    def run(library: bool):
        agg = TpuAggregator(capacity=1 << 12, batch_size=32, now=now)
        idx = [agg.registry.get_or_assign(ca) for ca in cas]
        batch = packing.pack_entries([(der, idx[i]) for der, i in leaves],
                                     batch_size=32)
        with monkeypatch.context() as m:
            if not library:
                m.setattr(native, "load", lambda: None)
            first = agg.ingest_packed(batch.data, batch.length,
                                      batch.issuer_idx, batch.valid)
            again = agg.ingest_packed(batch.data, batch.length,
                                      batch.issuer_idx, batch.valid)
        assert first.was_unknown.sum() == 30 and not again.was_unknown.any()
        return agg

    metrics.set_sink(metrics.InMemSink())
    a = run(True)
    # Representatives: three names, and an issuer's two CRLDPs and its
    # lanes without one (a class too; the loop skips it).
    assert counters() == {"lanes": 30, "fallback_lanes": 0, "distinct": 12}
    metrics.set_sink(metrics.InMemSink())
    b = run(False)
    assert counters() == {"lanes": 30, "fallback_lanes": 30, "distinct": 12}
    assert a.dn_sets == b.dn_sets and len(a.dn_sets) == 3
    assert a.crl_sets == b.crl_sets and a.crl_sets
    assert a._dn_raw_seen == b._dn_raw_seen
    assert a._crl_raw_seen == b._crl_raw_seen
    assert a.metrics == b.metrics
    assert np.array_equal(a.issuer_totals, b.issuer_totals)
    assert a.drain().counts == b.drain().counts
