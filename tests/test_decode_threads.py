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
