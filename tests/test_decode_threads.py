"""Intra-chunk native decode threads: byte-exact across thread counts.

The persistent C++ worker pool (ctmr_native.cpp) splits
``ctmr_decode_entries`` / ``ctmr_extract_sidecars`` / ``ctmr_pack_ders``
over contiguous lane ranges. The determinism contract pinned here: for
ANY thread count, every output of the decode and sidecar passes is
byte-identical to the serial pass — per-lane arrays trivially (disjoint
writes), and the issuer grouping because per-chunk groups merge by DER
bytes in lane order, reproducing the serial first-appearance order.

The corpora deliberately include every status class (OK, BAD_B64,
BAD_LEAF, NO_CHAIN, precerts) and the sidecar fuzz includes
walker-REJECTED and undecidable lanes — the parity claim is about the
whole output surface, not just the happy path.
"""

import base64
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ct_mapreduce_tpu.native import available, leafpack

from tests import certgen
from tests.test_der_kernel import fixture_certs, pack

pytestmark = pytest.mark.skipif(
    not available(), reason="native library unavailable (no C++ compiler)")

DECODE_FIELDS = ("data", "length", "timestamp_ms", "entry_type", "status")


def _wire_corpus():
    """Mixed wire batch: clean x509 + precert entries, plus every
    malformed flavor the decoder classifies (bad base64, truncated
    leaves, chainless entries, garbage extra_data)."""
    from ct_mapreduce_tpu.ingest import leaf as leaflib

    rng = np.random.default_rng(20260804)
    issuer = certgen.make_cert(serial=1, issuer_cn="MT CA", is_ca=True)
    lis, eds = [], []
    for j in range(600):
        leaf = certgen.make_cert(serial=1000 + j, issuer_cn="MT CA")
        li = leaflib.encode_leaf_input(leaf, timestamp_ms=1700000000000 + j)
        ed = leaflib.encode_extra_data([issuer])
        li_b64 = base64.b64encode(li).decode()
        ed_b64 = base64.b64encode(ed).decode()
        kind = j % 6
        if kind == 1:  # bad base64 character
            li_b64 = li_b64[:7] + "!" + li_b64[8:]
        elif kind == 2:  # truncated leaf bytes
            li_b64 = base64.b64encode(li[: int(rng.integers(1, 12))]).decode()
        elif kind == 3:  # no chain
            ed_b64 = ""
        elif kind == 4:  # mutated extra_data bytes
            raw = bytearray(ed)
            raw[int(rng.integers(len(raw)))] ^= int(rng.integers(1, 256))
            ed_b64 = base64.b64encode(bytes(raw)).decode()
        lis.append(li_b64)
        eds.append(ed_b64)
    return lis, eds


def _assert_batches_equal(a, b, ctx):
    for fld in DECODE_FIELDS:
        np.testing.assert_array_equal(
            getattr(a, fld), getattr(b, fld), err_msg=f"{ctx}: {fld}")
    np.testing.assert_array_equal(
        a.issuer_group, b.issuer_group, err_msg=f"{ctx}: issuer_group")
    assert a.group_issuers == b.group_issuers, ctx
    assert a.issuers == b.issuers, ctx


def test_decode_byte_exact_across_thread_counts():
    lis, eds = _wire_corpus()
    base = leafpack.decode_raw_batch(lis, eds, 2048, threads=1)
    # The corpus must actually exercise the status codes.
    assert len(set(base.status.tolist())) >= 4
    for t in (2, 3, 7, 16):
        got = leafpack.decode_raw_batch(lis, eds, 2048, threads=t)
        _assert_batches_equal(base, got, f"threads={t}")


@pytest.mark.parametrize("page", [1, 37, 600])
@pytest.mark.parametrize("threads", [1, 2, 3, 7, 16])
def test_page_table_decode_byte_exact_across_thread_counts(threads, page):
    """The same corpus as pages kept as bytes: the native call builds
    the pointer columns from the chunk's page table, and every thread
    count and page size gives the serial decode of the two lists; each
    thread's issuer slice is sized from the pages its lanes lie in, an
    upper bound that never made a chunk overflow into the retry."""
    from ct_mapreduce_tpu.telemetry import metrics

    lis, eds = _wire_corpus()
    base = leafpack.decode_raw_batch(lis, eds, 2048, threads=1)
    before = dict(metrics.get_sink().snapshot()["counters"])
    pages = _as_pages(lis, eds, page)
    got = leafpack.decode_raw_pages(pages, 2048, threads=threads)
    _assert_batches_equal(base, got, f"pages of {page}, threads={threads}")
    after = metrics.get_sink().snapshot()["counters"]
    assert after["decode.pages_tabled"] - before.get(
        "decode.pages_tabled", 0.0) == len(pages)
    assert after["decode.pages_walked"] == before.get(
        "decode.pages_walked", 0.0)


def test_sidecars_byte_exact_across_thread_counts_mutation_fuzz():
    """threads=N sidecar extraction over the SAME mutation-fuzz corpus
    test_preparsed.py pins against the device walker — including the
    walker-rejected (ok=0) and undecidable lanes, whose zeroed fields
    must also stitch back byte-exact."""
    rng = np.random.default_rng(20260804)
    bases = fixture_certs()
    mutants = []
    for _ in range(400):
        b = bytearray(bases[int(rng.integers(len(bases)))])
        for _k in range(int(rng.integers(1, 4))):
            b[int(rng.integers(len(b)))] ^= int(rng.integers(1, 256))
        mutants.append(bytes(b))
    data, length = pack(mutants, pad_to=1024)
    base = leafpack.extract_sidecars(data, length, threads=1)
    rejected = int((base.ok == 0).sum())
    assert rejected > 10, "fuzz corpus must include rejected lanes"
    for t in (2, 5, 13):
        got = leafpack.extract_sidecars(data, length, threads=t)
        for fld in vars(base):
            np.testing.assert_array_equal(
                getattr(base, fld), getattr(got, fld),
                err_msg=f"threads={t}: sidecar {fld}")


def test_pack_ders_byte_exact_across_thread_counts():
    rng = np.random.default_rng(7)
    ders = [bytes(rng.integers(0, 256, int(rng.integers(1, 900)),
                               dtype=np.uint8).tobytes())
            for _ in range(300)]
    ders.append(b"\x00" * 700)  # oversize lane (pad 512): length 0, ok 0
    base = leafpack.pack_ders(ders, 512, threads=1)
    for t in (2, 9):
        got = leafpack.pack_ders(ders, 512, threads=t)
        for i in range(3):
            np.testing.assert_array_equal(base[i], got[i])
        assert base[3] == got[3]
    want = sum(1 for d in ders if len(d) <= 512)
    assert base[3] == want and want < len(ders)  # oversize lanes skipped


def test_resolve_threads_policy(monkeypatch):
    """Explicit > env CTMR_DECODE_THREADS > legacy CTMR_DECODE_WORKERS
    > cpu count; auto keeps >= 2048 lanes per chunk."""
    monkeypatch.delenv("CTMR_DECODE_THREADS", raising=False)
    monkeypatch.delenv("CTMR_DECODE_WORKERS", raising=False)
    assert leafpack.resolve_threads(100, 8) == 8  # explicit wins, any n
    assert leafpack.resolve_threads(3, 8) == 3  # clamped to lanes
    assert leafpack.resolve_threads(1000) == 1  # small batch → serial
    monkeypatch.setenv("CTMR_DECODE_THREADS", "3")
    assert leafpack.resolve_threads(1 << 20) == 3
    monkeypatch.setenv("CTMR_DECODE_THREADS", "0")
    monkeypatch.setenv("CTMR_DECODE_WORKERS", "2")
    assert leafpack.resolve_threads(1 << 20) == 2


def test_legacy_workers_alias_routes_through_pool():
    """decode_raw_batch(workers=N) — the pre-pool knob — must keep
    producing identical results through the native worker pool."""
    lis, eds = _wire_corpus()
    a = leafpack.decode_raw_batch(lis, eds, 2048, workers=1)
    b = leafpack.decode_raw_batch(lis, eds, 2048, workers=4)
    _assert_batches_equal(a, b, "workers=4")


# -- issuer grouping without the buffer copy (PR 26) ----------------------

_CORPORA: dict = {}


def _grouping_corpus(name: str):
    """Small wire batches shaped around the issuer grouping (a few
    leaves, many lanes; chunks are ``n * t // T`` lane ranges):

    - ``interleaved``: three issuers round-robin, so every chunk of
      every thread count holds each issuer and the merge by DER bytes
      has to fold the chunks' copies into one group each;
    - ``nochain_chunk``: lanes [n/4, n/2) — one whole chunk at 4
      threads — carry no chain at all (``NO_CHAIN``), the others two
      issuers;
    - ``empty_extra``: every ``extra_data`` empty, no issuer anywhere;
    - ``late_issuer``: a second issuer that first appears in the last
      lanes only, after a run of bad lanes.
    """
    if name in _CORPORA:
        return _CORPORA[name]
    from ct_mapreduce_tpu.ingest import leaf as leaflib

    issuers = [certgen.make_cert(serial=1 + k, issuer_cn=f"Grp CA {k}",
                                 is_ca=True, key_seed=k) for k in range(3)]
    leaves = [certgen.make_cert(serial=5000 + j, issuer_cn=f"Grp CA {j % 3}")
              for j in range(6)]
    eds = [base64.b64encode(leaflib.encode_extra_data([c])).decode()
           for c in issuers]
    n = 208
    lis_out, eds_out = [], []
    for j in range(n):
        li = leaflib.encode_leaf_input(leaves[j % 6],
                                       timestamp_ms=1700000000000 + j)
        li_b64 = base64.b64encode(li).decode()
        if name == "interleaved":
            ed_b64 = eds[j % 3]
        elif name == "nochain_chunk":
            ed_b64 = "" if n // 4 <= j < n // 2 else eds[j % 2]
        elif name == "empty_extra":
            ed_b64 = ""
        else:  # late_issuer
            ed_b64 = eds[1] if j >= n - 5 else eds[0]
            if n - 40 <= j < n - 5:
                li_b64 = "!" + li_b64[1:]
        lis_out.append(li_b64)
        eds_out.append(ed_b64)
    want = leafpack._decode_python(lis_out, eds_out, 2048)
    _CORPORA[name] = (lis_out, eds_out, want)
    return _CORPORA[name]


def _as_pages(lis, eds, page: int) -> list:
    """The two columns cut into ``EntryPage``s of ``page`` entries: the
    form the downloader enqueues, which goes to the decoder as a page
    table (PR 39)."""
    return [leafpack.page_of_strings(lis[a:a + page], eds[a:a + page])
            for a in range(0, len(lis), page)]


@pytest.mark.parametrize("form", ["str", "bytes", "pages"])
@pytest.mark.parametrize("threads", [1, 2, 4, 13])
@pytest.mark.parametrize(
    "corpus", ["interleaved", "nochain_chunk", "empty_extra", "late_issuer"])
def test_issuer_groups_match_python_lane(corpus, threads, form):
    """Every thread count, ``str`` and ``bytes`` columns and a page
    table alike, gives the pure-Python lane's batch: arrays, group ids,
    group order."""
    lis, eds, want = _grouping_corpus(corpus)
    if form == "bytes":
        lis = [s.encode() for s in lis]
        eds = [s.encode() for s in eds]
    if form == "pages":  # 19 a page: no chunk of any count ends on one
        got = leafpack.decode_raw_pages(_as_pages(lis, eds, 19), 2048,
                                        threads=threads)
    else:
        got = leafpack.decode_raw_batch(lis, eds, 2048, threads=threads)
    _assert_batches_equal(want, got, f"{corpus} threads={threads}")
    n_groups = {"interleaved": 3, "nochain_chunk": 2, "empty_extra": 0,
                "late_issuer": 2}[corpus]
    assert len(got.group_issuers) == n_groups
    if corpus == "nochain_chunk":
        n = len(lis)
        assert set(got.status[n // 4: n // 2].tolist()) == {leafpack.NO_CHAIN}
        assert (got.issuer_group[n // 4: n // 2] == -1).all()


def test_issuer_groups_materialise_spans_not_the_buffer():
    """Grouping over a 64 MB shared issuer buffer (13 chunks' slices)
    allocates what its lanes and spans need (index arrays of a few
    bytes a lane, 13 x 16 DERs): under 1 MB, not the buffer."""
    import tracemalloc

    from ct_mapreduce_tpu.telemetry import trace

    chunks, each, lanes = 13, (64 << 20) // 13 + 1, 601
    buf = np.zeros((chunks * each,), np.uint8)
    n = chunks * lanes
    off = np.zeros((n,), np.int64)
    ln = np.zeros((n,), np.int32)
    ders = [bytes([k + 1]) * (1200 + 17 * k) for k in range(16)]
    for c in range(chunks):
        pos = c * each
        spans = []
        for der in ders:  # every chunk wrote its own copy of each DER
            buf[pos:pos + len(der)] = np.frombuffer(der, np.uint8)
            spans.append((pos, len(der)))
            pos += len(der)
        for i in range(lanes):
            j = c * lanes + i
            if i % 7:
                off[j], ln[j] = spans[(i * 5 + c) % 16]
    trace.enable(ring_size=64)
    try:
        tracemalloc.start()
        group, group_issuers = leafpack._issuer_groups(
            off, ln, buf, chunks=chunks)
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        events = [e for e in trace.snapshot_events()
                  if e["name"] == "decode.issuer_groups"]
    finally:
        trace.disable()
    assert peak < (1 << 20), peak
    # One group per DER whatever chunk wrote it, in the order the first
    # chunk's slice holds them (the decoder appends on first appearance).
    assert group_issuers == ders
    assert (group[ln == 0] == -1).all()
    for j in (1, lanes + 2, n - 1):
        assert group_issuers[group[j]] == bytes(
            buf[off[j]:off[j] + ln[j]])
    args = events[-1]["args"]
    assert args["chunks"] == 13 and args["groups"] == 16
    assert args["bytes"] == 13 * sum(len(d) for d in ders) < (1 << 20)


def test_traceview_batches_has_issuer_groups_and_self_is_the_residue(
        tmp_path):
    """``traceview --batches``: a column for ``decode.issuer_groups``,
    and ``native.decode_batch`` shown as what its children leave."""
    from ct_mapreduce_tpu.telemetry import trace
    from tools import traceview

    lis, eds, _want = _grouping_corpus("interleaved")
    trace.enable(ring_size=256)
    try:
        with trace.span("ingest.decode", cat="ingest", batch=3):
            leafpack.decode_raw_batch(lis, eds, 2048, threads=4)
        path = trace.export(str(tmp_path / "ring.json"))
    finally:
        trace.disable()
    events = traceview.load(path)
    (row,) = traceview.batch_table(events)
    assert row["batch"] == 3 and row["threads"] == 4
    assert "decode.issuer_groups" in traceview.BATCH_COLUMNS
    kids = sum(row[c] for c in ("decode.concat_b64", "decode.native_call",
                                "decode.issuer_groups"))
    whole = next(e["dur"] for e in events
                 if e.get("name") == "native.decode_batch") / 1e3
    assert row["native.decode_batch"] == pytest.approx(whole - kids)
    assert 0 <= row["native.decode_batch"] < whole
    assert traceview.main([path, "--batches"]) == 0
