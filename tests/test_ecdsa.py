"""Verification kernels: known-answer corpus + parity fuzz.

The contract under test: BOTH batched device formulations — the
windowed-precompute ladder (round 17, the default) and the legacy
Jacobian Shamir ladder (window = 0) — are bit-identical to the
pure-python reference verifier (verify/host.verify_ecdsa) on EVERY
input: valid signatures, Wycheproof-style edge classes (r/s = 0,
r/s ≥ n, non-canonical s, off-curve and out-of-range public keys,
wrong digests), windowed-ladder edge cases (u1 = 0, all-zero window
digits, point-at-infinity intermediates, accumulator/table-point
collisions, zero-denominator lanes inside the batch-inversion
product), and a ≥400-case mutation fuzz — on P-256 AND P-384. The
native SCT extraction pass (ctmr_extract_scts) stays bit-identical to
its python mirror (verify/sct.extract_scts_np).

Compile budget: each (curve, window, width) shape is its own ~15-20 s
XLA compile on the 1-core CI box, so tier-1 pays exactly THREE
compiles — legacy P-256, windowed P-256, windowed P-384, all at the
shared width 32 (and the lane tests reuse the windowed ones). The multi-window/multi-width sweeps and the 416-case fuzz
matrix run as ``slow`` tests.
"""

import hashlib
import os
import random
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ct_mapreduce_tpu.ops import bigint, ecdsa  # noqa: E402
from ct_mapreduce_tpu.verify import host, sct as sctlib  # noqa: E402

C = host.P256
C384 = host.P384
WIDTH = 32
W = ecdsa.DEFAULT_WINDOW  # the tier-1 windowed compile (8)


def _bn(v: int, nbytes: int = 32) -> np.ndarray:
    return np.frombuffer(
        (v % (1 << (8 * nbytes))).to_bytes(nbytes, "big"), np.uint8
    ).copy()


def _b32(v: int) -> np.ndarray:
    return _bn(v, 32)


def _key(seed: str, c: host.Curve = C):
    d = host.derive_scalar(seed, c)
    return d, host._point_mul(c, d, (c.gx, c.gy))


def _sign(seed: str, msg: bytes, c: host.Curve = C):
    d, q = _key(seed, c)
    digest = hashlib.sha256(msg).digest()
    r, s = host.sign_ecdsa(c, digest, d, host.derive_nonce(seed, msg, c))
    return digest, r, s, q


def _rows_to_arrays(rows, c: host.Curve = C):
    nb = c.byte_len
    digest = np.stack([np.frombuffer(dg, np.uint8) for dg, *_ in rows])
    r = np.stack([_bn(ri, nb) for _dg, ri, *_ in rows])
    s = np.stack([_bn(si, nb) for _dg, _r, si, *_ in rows])
    qx = np.stack([_bn(xi, nb) for *_x, xi, _yi in rows])
    qy = np.stack([_bn(yi, nb) for *_x, yi in rows])
    return digest, r, s, qx, qy


def _dverify(rows, width: int = WIDTH, window: int = W,
             c: host.Curve = C):
    """Device verdicts for [(digest, r, s, x, y)] int/bytes tuples at
    an explicit padded width (pow2; 32 is the shared tier-1 shape)."""
    assert len(rows) <= width
    n = len(rows)
    nb = c.byte_len
    digest = np.zeros((width, 32), np.uint8)
    r, s, qx, qy = (np.zeros((width, nb), np.uint8) for _ in range(4))
    dg_a, r_a, s_a, qx_a, qy_a = _rows_to_arrays(rows, c)
    digest[:n], r[:n], s[:n], qx[:n], qy[:n] = dg_a, r_a, s_a, qx_a, qy_a
    valid = np.zeros((width,), bool)
    valid[:n] = True
    fn = ecdsa.verify_p256 if c is C else ecdsa.verify_p384
    out = fn(digest, r, s, qx, qy, valid, window=window)
    return out[:n].tolist()


def _hverify(rows, c: host.Curve = C):
    lim = 1 << (8 * c.byte_len)
    return [
        host.verify_ecdsa(c, dg, ri % lim, si % lim, xi % lim, yi % lim)
        for dg, ri, si, xi, yi in rows
    ]


def _kat_corpus(c: host.Curve = C):
    """(name, row, expected) — the pinned edge classes."""
    cases = []
    dg, r, s, q = _sign("kat-a", b"hello ct", c)
    dg2, r2, s2, q2 = _sign("kat-b", b"second key", c)
    lim = 1 << (8 * c.byte_len)
    cases += [
        ("valid-a", (dg, r, s, q[0], q[1]), True),
        ("valid-b", (dg2, r2, s2, q2[0], q2[1]), True),
        ("wrong-digest", (hashlib.sha256(b"x").digest(), r, s, q[0], q[1]),
         False),
        ("wrong-key", (dg, r, s, q2[0], q2[1]), False),
        ("r-zero", (dg, 0, s, q[0], q[1]), False),
        ("s-zero", (dg, r, 0, q[0], q[1]), False),
        ("r-eq-n", (dg, c.n, s, q[0], q[1]), False),
        ("s-eq-n", (dg, r, c.n, q[0], q[1]), False),
        ("r-over-n", (dg, c.n + 5, s, q[0], q[1]), False),
        ("s-over-n", (dg, r, (c.n + r) % lim, q[0], q[1]), False),
        # (r, n - s) is the alternate encoding of a VALID signature —
        # plain ECDSA accepts the non-canonical s.
        ("noncanonical-s", (dg, r, c.n - s, q[0], q[1]), True),
        ("swapped-rs", (dg, s, r, q[0], q[1]), False),
        ("pub-off-curve", (dg, r, s, q[0], q[1] ^ 1), False),
        ("pub-zero", (dg, r, s, 0, 0), False),
        ("pub-x-eq-p", (dg, r, s, c.p, q[1]), False),
        ("pub-y-over-p", (dg, r, s, q[0], c.p + q[1]), False),
        # x = 0 with a matching on-curve y: y^2 = b — may not have a
        # root; use negated-y instead (on curve, wrong key half).
        ("pub-neg-y", (dg, r, s, q[0], c.p - q[1]), False),
    ]
    return cases


def _window_edge_corpus(c: host.Curve = C):
    """(name, row, expected) — the round-17 windowed-ladder edge
    classes, each constructed from the group math so the interesting
    condition REALLY occurs mid-ladder. The SHA-256 digest bounds
    z < 2^256, so the cases needing z to hit an arbitrary mod-n value
    (valid u1 = 1 / valid doubling collisions) exist only on P-256;
    P-384 pins the same ladder states through False-verdict rows."""
    d, q = _key("edge-a", c)
    # u1 = 0 (every G window digit zero): zero digest → z = 0;
    # s = r·d·k⁻¹ makes u2·Q = k·G, so the signature is VALID with the
    # G side of the dual scalar contributing nothing.
    k = host.derive_nonce("edge-a", b"u1zero", c)
    rp = host._point_mul(c, k, (c.gx, c.gy))
    r0 = rp[0] % c.n
    s0 = r0 * d % c.n * pow(k, -1, c.n) % c.n
    # Q = -G with u1 = u2 (digest bytes = r): every window's G-add is
    # cancelled by its Q-add — the accumulator passes through the
    # point at infinity REPEATEDLY mid-ladder, and the result is
    # infinity (verdict False; host sees R = None).
    rx = 0x1234_5678_9ABC_DEF0_1357
    # Accumulator == table point (the P = Q doubling collision the
    # complete formulas must absorb): u1 = 2, u2 = 1, Q = 2G — after
    # the window-0 G-add the accumulator is 2G and the Q-add folds in
    # the SAME affine point. s = z·2⁻¹ and r = s force those scalars
    # for any digest z (False verdict: r is not x(4G)).
    q2g = host._point_mul(c, 2, (c.gx, c.gy))
    z_c = int.from_bytes(hashlib.sha256(b"collide").digest(), "big")
    s_c = z_c * pow(2, -1, c.n) % c.n
    cases = [
        ("u1-zero", (bytes(32), r0, s0, q[0], q[1]), True),
        ("mid-ladder-infinity",
         (rx.to_bytes(32, "big"), rx, 7, c.gx, c.p - c.gy), False),
        ("dbl-collision-false",
         (z_c.to_bytes(32, "big"), s_c, s_c, q2g[0], q2g[1]), False),
    ]
    if c is C:
        # u1 = 1: z = r·d·(k-1)⁻¹ and s = z — every u1 window digit
        # above the lowest is zero, and the signature stays VALID.
        k1 = host.derive_nonce("edge-b", b"u1one", c)
        r1 = host._point_mul(c, k1, (c.gx, c.gy))[0] % c.n
        z1 = r1 * d % c.n * pow(k1 - 1, -1, c.n) % c.n
        # Valid doubling collision: u1 = 2, u2 = 1, r = x(4G), s = r,
        # z = 2r — same ladder state as above but the verdict is True.
        r4 = host._point_mul(c, 4, (c.gx, c.gy))[0] % c.n
        cases += [
            ("u1-one-zero-digits",
             (z1.to_bytes(32, "big"), r1, z1, q[0], q[1]), True),
            ("dbl-collision-valid",
             ((2 * r4 % c.n).to_bytes(32, "big"), r4, r4,
              q2g[0], q2g[1]), True),
        ]
    return cases


def _run_corpus(cases, window: int, c: host.Curve = C):
    rows = [cs[1] for cs in cases]
    expected = [cs[2] for cs in cases]
    hv = _hverify(rows, c)
    assert hv == expected, [cs[0] for cs, h, e in
                            zip(cases, hv, expected) if h != e]
    dv = _dverify(rows, window=window, c=c)
    assert dv == expected, (window, [cs[0] for cs, d, e in
                                     zip(cases, dv, expected) if d != e])


def test_known_answer_corpus():
    """The full KAT corpus pinned host == windowed == legacy (the two
    tier-1 P-256 compiles)."""
    cases = _kat_corpus()
    _run_corpus(cases, window=W)
    _run_corpus(cases, window=0)


def test_windowed_edge_cases():
    """Round-17 windowed-ladder edges, pinned bit-identical vs the
    host reference AND vs the legacy (window = 0) ladder."""
    cases = _window_edge_corpus()
    _run_corpus(cases, window=W)
    _run_corpus(cases, window=0)


def test_batch_inversion_zero_lane_isolation():
    """Batches mixing zero-denominator lanes into the batch-inversion
    product: s = 0 lanes (zero through the s⁻¹ product) and
    R-at-infinity lanes (zero through the x_R = X/Z normalization)
    interleaved with valid lanes — every lane answers exactly what it
    answers alone (adversarial inputs cannot desync a neighbor)."""
    dg, r, s, q = _sign("iso-a", b"isolation")
    inf_row = _window_edge_corpus()[2][1]  # R = infinity lane
    rows = [
        (dg, r, s, q[0], q[1]),
        (dg, r, 0, q[0], q[1]),  # s = 0
        (dg, r, s, q[0], q[1]),
        inf_row,  # Z = 0 in the final normalization
        (dg, r, C.n - s, q[0], q[1]),  # still valid (non-canonical s)
        (hashlib.sha256(b"no").digest(), r, s, q[0], q[1]),  # failed
    ]
    batch = _dverify(rows, window=W)
    assert batch == _hverify(rows)
    for i, row in enumerate(rows):
        assert _dverify([row], window=W) == [batch[i]], i


def test_all_valid_and_all_invalid_batches():
    valid_rows = []
    for i in range(WIDTH):
        dg, r, s, q = _sign(f"fill-{i % 5}", b"m%d" % i)
        valid_rows.append((dg, r, s, q[0], q[1]))
    assert _dverify(valid_rows, window=0) == [True] * WIDTH
    invalid_rows = [(dg, 0, s, x, y) for dg, _r, s, x, y in valid_rows]
    assert _dverify(invalid_rows, window=0) == [False] * WIDTH


def test_padding_mask_parity():
    """Verdicts are invariant to where lanes sit in the padded batch:
    the same rows scattered behind invalid filler lanes answer
    identically (the valid mask really gates, padding garbage cannot
    leak into live lanes)."""
    cases = _kat_corpus()[:10]
    rows = [cs[1] for cs in cases]
    base = _dverify(rows, window=W)
    filler = _sign("pad-filler", b"pad")
    mixed = []
    for row in rows:
        mixed.append((filler[0], 0, 0, 0, 0))  # dead-invalid lane
        mixed.append(row)
    out = _dverify(mixed, window=W)
    assert out[1::2] == base


def test_p384_known_answer_corpus():
    """The P-384 device lane's own KAT corpus (full edge classes +
    windowed edges), verdict-bit-identical to the host reference —
    the ONE tier-1 P-384 compile (windowed, width 32; the lane tests
    reuse it)."""
    cases = _kat_corpus(C384) + _window_edge_corpus(C384)
    _run_corpus(cases, window=W, c=C384)


@pytest.mark.slow
def test_p384_window0_parity():
    """P-384 through the legacy (window = 0) Jacobian ladder — its
    own 384-iteration compile, so slow-tier; the windowed↔legacy↔host
    triangle is tier-1 on P-256 and the P-384 windowed↔host edge is
    tier-1 above."""
    cases = _kat_corpus(C384) + _window_edge_corpus(C384)
    _run_corpus(cases, window=0, c=C384)


@pytest.mark.slow
def test_batch_width_parity_wide():
    """Same lanes at freshly-compiled widths 64 and 128 → identical
    verdicts (width-invariance of the pow2-padded dispatch). Slow:
    each width is its own XLA compile on the CI box."""
    cases = _kat_corpus()
    rows = [cs[1] for cs in cases]
    expected = [cs[2] for cs in cases]
    assert _dverify(rows, width=64, window=0) == expected
    assert _dverify(rows, width=128, window=0) == expected
    assert _dverify(rows, width=64, window=W) == expected


@pytest.mark.slow
@pytest.mark.parametrize("window,curve", [
    (0, "p256"), (2, "p256"), (4, "p256"), (8, "p256"),
    (0, "p384"), (8, "p384"),
])
def test_mutation_fuzz_device_host_parity(window, curve):
    """≥400 mutated signatures (P-256; 128 for the slower P-384
    host reference): the device verdict equals the host verdict on
    every lane at every (window, curve) configuration, including the
    window = 0 legacy path (acceptance gate). Mutations hit every
    input field; ~1/8 lanes are left untouched (valid).

    @slow since round 15 (tier-1 budget banking): the verdict-parity
    contract stays tier-1-gated by the KAT corpora, the windowed-edge
    and zero-lane-isolation batches, and tests/test_verify_lane.py; this sweep re-walks the same kernels per configuration."""
    c = C if curve == "p256" else C384
    nbits = 8 * c.byte_len
    rng = random.Random(0x5C7 + window)
    count = 13 * WIDTH if curve == "p256" else 4 * WIDTH
    rows = []
    for i in range(count):
        dg, r, s, q = _sign(f"fuzz-{i % 7}", b"fz%d" % (i % 29), c)
        row = [bytearray(dg), r, s, q[0], q[1]]
        kind = rng.randrange(8)
        if kind == 1:
            row[0][rng.randrange(32)] ^= 1 << rng.randrange(8)
        elif kind == 2:
            row[1] ^= 1 << rng.randrange(nbits)
        elif kind == 3:
            row[2] ^= 1 << rng.randrange(nbits)
        elif kind == 4:
            row[3] ^= 1 << rng.randrange(nbits)
        elif kind == 5:
            row[4] ^= 1 << rng.randrange(nbits)
        elif kind == 6:
            row[rng.randrange(1, 5)] = rng.getrandbits(nbits)
        elif kind == 7:
            row[2] = c.n - row[2]  # stays valid
        rows.append((bytes(row[0]), row[1], row[2], row[3], row[4]))
    mismatches = []
    for lo in range(0, len(rows), WIDTH):
        chunk = rows[lo : lo + WIDTH]
        dv = _dverify(chunk, window=window, c=c)
        hv = _hverify(chunk, c)
        mismatches += [lo + j for j, (d, h) in enumerate(zip(dv, hv))
                       if d != h]
    assert not mismatches, mismatches
    assert len(rows) >= (400 if curve == "p256" else 128)


# -- big-int layer -------------------------------------------------------

@pytest.mark.parametrize("mod,p_int", [
    (bigint.P256_P, bigint.P256_P_INT),
    (bigint.P384_P, bigint.P384_P_INT),
])
def test_montgomery_arithmetic_against_python_ints(mod, p_int):
    import jax

    rng = random.Random(7)
    nbits = bigint.RADIX * mod.nlimb
    a_int = [rng.getrandbits(nbits) % p_int for _ in range(32)]
    b_int = [rng.getrandbits(nbits) % p_int for _ in range(32)]
    a = np.stack([bigint.limbs_from_int(v, mod.nlimb) for v in a_int])
    b = np.stack([bigint.limbs_from_int(v, mod.nlimb) for v in b_int])

    @jax.jit
    def modmul(a, b):
        am = bigint.to_mont(a, mod)
        bm = bigint.to_mont(b, mod)
        return (
            bigint.from_mont(bigint.mont_mul(am, bm, mod), mod),
            bigint.add_mod(a, b, mod),
            bigint.sub_mod(a, b, mod),
        )

    prod, s, d = modmul(a, b)
    for i in range(32):
        assert bigint.int_from_limbs(np.asarray(prod)[i]) \
            == a_int[i] * b_int[i] % p_int
        assert bigint.int_from_limbs(np.asarray(s)[i]) \
            == (a_int[i] + b_int[i]) % p_int
        assert bigint.int_from_limbs(np.asarray(d)[i]) \
            == (a_int[i] - b_int[i]) % p_int


def test_mont_inv_random():
    import jax

    rng = random.Random(9)
    mod = bigint.P256_N
    vals = [rng.getrandbits(255) % (bigint.P256_N_INT - 1) + 1
            for _ in range(8)]
    a = np.stack([bigint.limbs_from_int(v) for v in vals])

    @jax.jit
    def inv(a):
        return bigint.from_mont(
            bigint.mont_inv(bigint.to_mont(a, mod), mod), mod)

    out = np.asarray(inv(a))
    for i, v in enumerate(vals):
        assert bigint.int_from_limbs(out[i]) \
            == pow(v, -1, bigint.P256_N_INT)


@pytest.mark.parametrize("mod,n_int", [
    (bigint.P256_N, bigint.P256_N_INT),
    (bigint.P384_N, bigint.P384_N_INT),
])
def test_batch_inv_mont_matches_fermat(mod, n_int):
    """batch_inv_mont ≡ pow(v, -1, n) per lane, with zero lanes
    (masked through the product) inverting to zero and not disturbing
    their neighbors."""
    import jax

    rng = random.Random(11)
    nbits = bigint.RADIX * mod.nlimb
    vals = [rng.getrandbits(nbits - 1) % (n_int - 1) + 1
            for _ in range(12)]
    vals[3] = 0
    vals[7] = 0
    a = np.stack([bigint.limbs_from_int(v, mod.nlimb) for v in vals])

    @jax.jit
    def binv(a):
        return bigint.from_mont(
            bigint.batch_inv_mont(bigint.to_mont(a, mod), mod), mod)

    out = np.asarray(binv(a))
    for i, v in enumerate(vals):
        got = bigint.int_from_limbs(out[i])
        assert got == (pow(v, -1, n_int) if v else 0), i


def test_point_table_independently_derivable():
    """Window-table entries equal d·2^(w·j)·G computed through the
    reference scalar multiplication — the precompute constants are
    derivable without the incremental builder that made them."""
    tab = ecdsa.point_table_np(C, C.gx, C.gy, 8)
    r_mont = 1 << 256
    for j, d in ((0, 1), (0, 255), (3, 17), (31, 2)):
        pt = host._point_mul(C, d << (8 * j), (C.gx, C.gy))
        assert bigint.int_from_limbs(tab[j, d, 0]) \
            == pt[0] * r_mont % C.p
        assert bigint.int_from_limbs(tab[j, d, 1]) \
            == pt[1] * r_mont % C.p
    assert not tab[:, 0].any()  # digit 0 = identity slots stay zero


# -- extraction parity ---------------------------------------------------

def _sct_corpus():
    from ct_mapreduce_tpu.utils import minicert

    base = minicert.make_cert(serial=7, issuer_cn="Extract CA",
                              crl_dps=("http://crl.example/x",))
    plain = minicert.make_cert(serial=9, issuer_cn="NoExt CA",
                               add_basic_constraints=False)
    p256 = sctlib.EcSctSigner("ext-a")
    p384 = sctlib.EcSctSigner("ext-b", host.P384)
    rsa = sctlib.RsaSctSigner()
    certs = [
        sctlib.attach_sct(base, p256, 1_700_000_000_000),
        sctlib.attach_sct(base, p256, 1_700_000_000_001,
                          corrupt_signature=True),
        sctlib.attach_sct(base, p384, 1_700_000_000_002),
        sctlib.attach_sct(base, rsa, 1_700_000_000_003),
        base,
        sctlib.attach_sct(base, p256, 1_700_000_000_004,
                          extensions=b"hello"),
        sctlib.attach_sct(plain, p256, 5),
    ]
    rng = random.Random(1)
    for k in range(64):
        c = bytearray(certs[k % 7])
        for _ in range(rng.randrange(1, 4)):
            c[rng.randrange(len(c))] ^= 1 << rng.randrange(8)
        certs.append(bytes(c))
    pad = max(len(c) for c in certs) + 32
    data = np.zeros((len(certs), pad), np.uint8)
    length = np.zeros((len(certs),), np.int32)
    for i, c in enumerate(certs):
        data[i, : len(c)] = np.frombuffer(c, np.uint8)
        length[i] = len(c)
    return data, length


def test_sct_extraction_classes():
    data, length = _sct_corpus()
    out = sctlib.extract_scts_np(data, length)
    assert out.ok[:7].tolist() == [1, 1, 2, 2, 0, 1, 1]


def test_native_extraction_parity():
    from ct_mapreduce_tpu.native import available, leafpack

    if not available() or not getattr(
            __import__("ct_mapreduce_tpu.native", fromlist=["load"]).load(),
            "has_sct", False):
        pytest.skip("native SCT extractor unavailable")
    data, length = _sct_corpus()
    py = sctlib.extract_scts_np(data, length)
    for threads in (1, 4):
        nat = leafpack.extract_scts(data, length, threads=threads)
        for fld in ("ok", "digest", "log_id", "timestamp_ms", "r", "s",
                    "hash_alg", "sig_alg"):
            assert np.array_equal(getattr(nat, fld), getattr(py, fld)), \
                (threads, fld)


def test_extract_scts_python_fallback(monkeypatch):
    """CTMR_NATIVE=0 routes leafpack.extract_scts down the python
    mirror — same outputs (the degradation contract)."""
    from ct_mapreduce_tpu.native import leafpack

    data, length = _sct_corpus()
    monkeypatch.setenv("CTMR_NATIVE", "0")
    fb = leafpack.extract_scts(data, length)
    monkeypatch.delenv("CTMR_NATIVE")
    py = sctlib.extract_scts_np(data, length)
    assert np.array_equal(fb.ok, py.ok)
    assert np.array_equal(fb.digest, py.digest)


def test_registry_json_roundtrip(tmp_path):
    from ct_mapreduce_tpu.verify.lane import LogKeyRegistry

    reg = LogKeyRegistry()
    signers = [sctlib.EcSctSigner("rt-a"),
               sctlib.EcSctSigner("rt-b", host.P384),
               sctlib.RsaSctSigner()]
    for s in signers:
        reg.register_signer(s)
    # exercise the coordinate cache, then round-trip (the "_"-prefixed
    # runtime caches — coords, registry epoch — must not serialize)
    from ct_mapreduce_tpu.verify.lane import _key_coord

    _key_coord(reg.get(signers[0].log_id), "x")
    assert reg.epoch == 3
    path = tmp_path / "keys.json"
    path.write_text(reg.to_json())
    assert "_epoch" not in path.read_text()
    reg2 = LogKeyRegistry.from_json_file(str(path))
    assert len(reg2) == 3
    assert reg2.is_p256(signers[0].log_id)
    assert not reg2.is_p256(signers[1].log_id)
    assert reg2.get(signers[2].log_id)["alg"] == "rsa"
