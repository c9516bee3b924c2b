"""Real-log audit subsystem (round 24): RFC 6962 §3.2 TBS
reconstruction (KAT + poison-placement edges + mutation fuzz),
production log-list loading/routing, the quarantine lane's exclusion
property, and the recorded-shard driver feeding every existing
downstream surface.

The reconstruction contract under test: the digest convention is the
REAL precert signing digest — TBSCertificate with every SCT-list and
poison extension stripped and outer lengths re-encoded — computed
bit-identically by the native streaming scanner and the pure-python
mirror. Any lane where they disagree is quarantined and provably
excluded from aggregates (counts identical with the lane spooled or
the entry dropped).
"""

import base64
import datetime
import hashlib
import json
import os

import numpy as np
import pytest

from ct_mapreduce_tpu.audit import driver as drvlib
from ct_mapreduce_tpu.audit import fixture as fxlib
from ct_mapreduce_tpu.audit import loglist as loglistlib
from ct_mapreduce_tpu.audit import quarantine as quarlib
from ct_mapreduce_tpu.ingest import leaf as leaflib
from ct_mapreduce_tpu.verify import host as vhost
from ct_mapreduce_tpu.verify import sct as sctlib


UTC = datetime.timezone.utc
FUTURE = datetime.datetime(2031, 6, 15, tzinfo=UTC)

TS_KAT = 1_710_000_000_000


def _native_sct_available() -> bool:
    try:
        from ct_mapreduce_tpu.native import load as load_native

        if os.environ.get("CTMR_NATIVE", "1") == "0":
            return False
        lib = load_native()
        return lib is not None and getattr(lib, "has_sct", False)
    except Exception:
        return False


needs_native = pytest.mark.skipif(
    not _native_sct_available(),
    reason="native SCT extractor unavailable")


# -- DER surgery helpers -----------------------------------------------------

# A poison extension (RFC 6962 §3.1): critical, extnValue = DER NULL.
POISON_EXT = sctlib._wrap_tlv(
    0x30,
    sctlib._wrap_tlv(0x06, sctlib.POISON_OID)
    + b"\x01\x01\xff"
    + sctlib._wrap_tlv(0x04, b"\x05\x00"),
)


def _with_exts(der: bytes, fn) -> bytes:
    """Rebuild ``der`` with its [3] extension list transformed by
    ``fn(list[raw_ext_tlv]) -> list[raw_ext_tlv]`` (empty result omits
    [3] entirely). Signature bytes ride along unchanged — reconstruction
    never looks at them."""
    t = sctlib._tlv(der, 0, len(der))
    _, cert_off, cert_len = t
    tbs = sctlib._tlv(der, cert_off, cert_off + cert_len)
    tbs_off, tbs_len = tbs[1], tbs[2]
    tbs_end = tbs_off + tbs_len
    rest = der[tbs_end:]
    off = tbs_off
    t2 = sctlib._tlv(der, off, tbs_end)
    if t2[0] == 0xA0:
        off = t2[1] + t2[2]
    for _ in range(6):
        t2 = sctlib._tlv(der, off, tbs_end)
        off = t2[1] + t2[2]
    head = der[tbs_off:off]
    exts: list[bytes] = []
    while off < tbs_end:
        t2 = sctlib._tlv(der, off, tbs_end)
        if t2[0] == 0xA3:
            seq = sctlib._tlv(der, t2[1], t2[1] + t2[2])
            p, p_end = seq[1], seq[1] + seq[2]
            while p < p_end:
                e = sctlib._tlv(der, p, p_end)
                exts.append(der[p:e[1] + e[2]])
                p = e[1] + e[2]
            off = t2[1] + t2[2]
            break
        head += der[off:t2[1] + t2[2]]
        off = t2[1] + t2[2]
    head += der[off:tbs_end]
    new = list(fn(exts))
    body = head
    if new:
        body += sctlib._wrap_tlv(0xA3, sctlib._wrap_tlv(0x30, b"".join(new)))
    return sctlib._wrap_tlv(
        0x30, sctlib._wrap_tlv(0x30, body) + rest)


def _kat_cert(**kw) -> bytes:
    """RNG-free bytes for the pinned digests: `certgen.make_cert` signs
    with a fresh random key wherever `cryptography` is installed."""
    from ct_mapreduce_tpu.utils import minicert

    return minicert.make_cert(
        org="Unit Test Corp", country="US", serial_len=None,
        spki_seed="certgen-key:0",
        not_before=datetime.datetime(2024, 1, 1, tzinfo=UTC),
        not_after=FUTURE, **kw)


def _kat_materials():
    issuer = _kat_cert(serial=1, issuer_cn="KAT CA", is_ca=True)
    leaf = _kat_cert(serial=7, issuer_cn="KAT CA",
                     subject_cn="kat.example", is_ca=False)
    signer = loglistlib.adopt_production_id(
        sctlib.EcSctSigner("audit-kat"))
    der = sctlib.attach_sct(leaf, signer, TS_KAT, issuer_der=issuer)
    return issuer, leaf, signer, der


# -- §3.2 TBS reconstruction -------------------------------------------------


def test_reconstruct_tbs_kat():
    """Known-answer pin for the reconstruction and the full signing
    digest: the fixture generators are RNG-free, so these values are
    stable across processes and boxes. A change here is a digest-
    convention change and must be deliberate (MIGRATING.md)."""
    issuer, leaf, _signer, der = _kat_materials()
    assert hashlib.sha256(der).hexdigest() == (
        "9be022a7e05cd26c7e235e761a4144905e9bc226"
        "abe832cb6b2dfcd488dc1f2a")
    tbs = sctlib.reconstruct_precert_tbs(der)
    assert hashlib.sha256(tbs).hexdigest() == (
        "6dc9519f8e53f57e38d6281fc50189b11bc08453"
        "1dbd684bc01d9d57bb9ff8cc")
    ikh = sctlib.issuer_key_hash_of(issuer)
    assert ikh.hex() == (
        "de101f1aaab1fc2e96277e9d0dbcd8b5f7046d8f"
        "90bccb328889c3accfd6187f")
    digest = sctlib.sct_digest(der, 0, 0, TS_KAT, b"", ikh)
    assert digest.hex() == (
        "85cc8981d21673ad6c1820e1421d45a5bd42ed12"
        "ae93262272d95975d7212cbf")
    # The reconstruction of the ORIGINAL (no SCT) leaf is identical —
    # stripping the embedded list recovers what the log signed.
    assert tbs == sctlib.reconstruct_precert_tbs(leaf)


def test_digest_structure_independently_rederived():
    """The §3.2 digitally-signed payload, rebuilt by hand from its
    documented layout, hashes to what sct_digest returns."""
    issuer, _leaf, _signer, der = _kat_materials()
    ikh = sctlib.issuer_key_hash_of(issuer)
    tbs = sctlib.reconstruct_precert_tbs(der)
    payload = (
        b"\x00"                       # version v1
        + b"\x00"                     # signature_type certificate_timestamp
        + TS_KAT.to_bytes(8, "big")   # timestamp
        + b"\x00\x01"                 # entry_type precert_entry
        + ikh                         # issuer_key_hash
        + len(tbs).to_bytes(3, "big") + tbs   # opaque TBSCertificate<1..2^24-1>
        + b"\x00\x00"                 # CtExtensions (empty)
    )
    assert hashlib.sha256(payload).digest() == sctlib.sct_digest(
        der, 0, 0, TS_KAT, b"", ikh)
    # issuer_key_hash really is SHA-256 over the issuer's SPKI TLV.
    win = sctlib.find_spki(issuer)
    assert ikh == hashlib.sha256(issuer[win[0]:win[1]]).digest()


def test_reconstruct_strips_poison_at_every_placement():
    """Poison extensions are stripped wherever they sit: first, every
    interior slot, last, and multiply — the reconstruction always
    equals the SCT-certificate's own reconstruction."""
    _issuer, _leaf, _signer, der = _kat_materials()
    expected = sctlib.reconstruct_precert_tbs(der)
    exts: list = []
    _with_exts(der, lambda e: exts.extend(e) or e)
    assert len(exts) >= 2  # base extensions + the SCT list
    for k in range(len(exts) + 1):
        poisoned = _with_exts(
            der, lambda e, k=k: e[:k] + [POISON_EXT] + e[k:])
        assert sctlib.reconstruct_precert_tbs(poisoned) == expected, k
    # Multiple poisons, both edges at once.
    double = _with_exts(
        der, lambda e: [POISON_EXT] + e + [POISON_EXT])
    assert sctlib.reconstruct_precert_tbs(double) == expected


def test_reconstruct_omits_empty_extension_list():
    """When stripping leaves no extensions, [3] is omitted entirely
    (§3.2: 'the Precertificate's TBSCertificate ... without the
    poison extension')."""
    _issuer, leaf, signer, _der = _kat_materials()
    bare = _with_exts(leaf, lambda e: [])  # no [3] at all
    assert sctlib.find_sct_extension(bare) is None
    only_poison = _with_exts(bare, lambda e: [POISON_EXT])
    tbs = sctlib.reconstruct_precert_tbs(only_poison)
    assert tbs == sctlib.reconstruct_precert_tbs(bare)
    # ... and the stripped TBS carries no [3] element at the tail.
    t = sctlib._tlv(tbs, 0, len(tbs))
    content = tbs[t[1]:t[1] + t[2]]
    assert b"\xa3" not in content[-4:]
    # SCT as the ONLY extension: same omission.
    only_sct = sctlib.attach_sct(bare, signer, TS_KAT)
    assert sctlib.reconstruct_precert_tbs(only_sct) \
        == sctlib.reconstruct_precert_tbs(bare)


def _pack_rows(ders: list) -> tuple:
    pad = max(len(d) for d in ders)
    data = np.zeros((len(ders), pad), np.uint8)
    length = np.zeros((len(ders),), np.int32)
    for j, d in enumerate(ders):
        data[j, :len(d)] = np.frombuffer(d, np.uint8)
        length[j] = len(d)
    return data, length


def _placement_variants() -> list:
    issuer, leaf, signer, der = _kat_materials()
    variants = [der]
    exts = []
    _with_exts(der, lambda e: exts.extend(e) or e)
    for k in range(len(exts) + 1):
        variants.append(_with_exts(
            der, lambda e, k=k: e[:k] + [POISON_EXT] + e[k:]))
    variants.append(_with_exts(
        der, lambda e: [POISON_EXT] + e + [POISON_EXT]))
    bare = _with_exts(leaf, lambda e: [])
    variants.append(sctlib.attach_sct(bare, signer, TS_KAT))
    variants.append(_with_exts(bare, lambda e: [POISON_EXT]))
    variants.append(leaf)  # no SCT at all
    return variants


@needs_native
def test_native_mirror_bit_identical_on_poison_edges():
    """The acceptance pin: the native streaming scanner and the
    Python mirror produce byte-identical extractions (digest included)
    across every poison-placement edge."""
    from ct_mapreduce_tpu.native import leafpack

    issuer, _leaf, _signer, _der = _kat_materials()
    variants = _placement_variants()
    data, length = _pack_rows(variants)
    ikh = np.tile(
        np.frombuffer(sctlib.issuer_key_hash_of(issuer), np.uint8),
        (len(variants), 1))
    native = leafpack.extract_scts(data, length, issuer_key_hash=ikh)
    mirror = sctlib.extract_scts_np(data, length, issuer_key_hash=ikh)
    chk = quarlib.compare_extractions(native, mirror)
    assert chk.measured and chk.count == 0, chk.reasons
    # The SCT-bearing variants all carry the SAME digest (poison and
    # placement never change what the log signed) — and it is the KAT.
    ok_rows = np.flatnonzero(mirror.ok == sctlib.SCT_OK)
    assert len(ok_rows) >= len(variants) - 3
    kat = sctlib.sct_digest(variants[0], 0, 0, TS_KAT, b"",
                            sctlib.issuer_key_hash_of(issuer))
    for j in ok_rows[:-1]:
        assert bytes(mirror.digest[j]) == kat, int(j)


@needs_native
def test_mutation_fuzz_native_mirror_agreement():
    """Byte-flip fuzz over the placement variants: whatever each
    extractor decides (accept, fallback, reject), they must decide it
    IDENTICALLY — the quarantine lane's steady-state-empty claim."""
    from ct_mapreduce_tpu.native import leafpack

    rng = np.random.default_rng(20260807)
    bases = _placement_variants()
    mutants = []
    for _ in range(240):
        base = bytearray(bases[int(rng.integers(len(bases)))])
        for _ in range(int(rng.integers(1, 4))):
            base[int(rng.integers(len(base)))] ^= int(
                rng.integers(1, 256))
        mutants.append(bytes(base))
    data, length = _pack_rows(mutants)
    native = leafpack.extract_scts(data, length)
    mirror = sctlib.extract_scts_np(data, length)
    chk = quarlib.compare_extractions(native, mirror)
    assert chk.measured and chk.count == 0, chk.reasons


# -- log-list schema ---------------------------------------------------------


def _fixture_list():
    signers = fxlib.fixture_signers()
    return signers, loglistlib.parse_log_list(
        fxlib.fixture_log_list_doc(signers))


def test_loglist_parses_production_shape():
    signers, ll = _fixture_list()
    assert len(ll) == 3  # p256 + p384(retired) + rsa; unknown unlisted
    assert ll.version == "3.99"
    p256 = ll.shards[signers["p256"].log_id]
    assert p256.state == "usable"
    assert p256.operator == "Audit Fixture Op"
    assert p256.entry["alg"] == "p256"
    assert p256.entry["log_id"] == signers["p256"].log_id.hex()
    assert ll.shards[signers["p384"].log_id].state == "retired"
    assert ll.shards[signers["rsa"].log_id].entry["alg"] == "rsa"
    # The registry the verify lane consumes resolves every listed id.
    reg = ll.registry()
    for name in ("p256", "p384", "rsa"):
        assert reg.get(signers[name].log_id) is not None
    assert reg.get(signers["unknown"].log_id) is None


def test_loglist_temporal_interval_boundaries():
    signers, ll = _fixture_list()
    start = loglistlib.parse_rfc3339_ms(fxlib.INTERVAL[0])
    end = loglistlib.parse_rfc3339_ms(fxlib.INTERVAL[1])
    shard = ll.shards[signers["p256"].log_id]
    assert shard.accepts_at(start)          # start is inclusive
    assert not shard.accepts_at(start - 1)
    assert shard.accepts_at(end - 1)
    assert not shard.accepts_at(end)        # end is exclusive
    v = ll.route(signers["p256"].log_id, end)
    assert v.known and not v.in_interval and not v.retired
    # Unsharded logs accept any timestamp.
    assert ll.route(signers["rsa"].log_id, 1).in_interval
    assert ll.route(signers["rsa"].log_id, 1 << 62).in_interval


def test_loglist_retired_is_verify_but_flag():
    signers, ll = _fixture_list()
    v = ll.route(signers["p384"].log_id, fxlib.TS_IN_INTERVAL)
    assert v.known and v.retired and v.state == "retired"
    # ... and its key still loads into the registry (verifiable).
    assert ll.registry().get(signers["p384"].log_id) is not None


def test_loglist_unknown_log_id():
    signers, ll = _fixture_list()
    v = ll.route(signers["unknown"].log_id, fxlib.TS_IN_INTERVAL)
    assert not v.known and v.state == ""


def test_loglist_key_logid_mismatch_is_loud():
    signers, _ = _fixture_list()
    doc = fxlib.fixture_log_list_doc(signers)
    raw = doc["operators"][0]["logs"][0]
    wrong = hashlib.sha256(b"not the key").digest()
    raw["log_id"] = base64.b64encode(wrong).decode()
    with pytest.raises(ValueError, match="SHA-256"):
        loglistlib.parse_log_list(doc)


def test_loglist_rejected_and_pending_skipped():
    s1 = loglistlib.adopt_production_id(
        sctlib.EcSctSigner("audit-rejected"))
    s2 = loglistlib.adopt_production_id(
        sctlib.EcSctSigner("audit-pending"))
    s3 = loglistlib.adopt_production_id(
        sctlib.EcSctSigner("audit-readonly"))
    doc = loglistlib.fixture_log_list([
        {"signer": s1, "state": "rejected"},
        {"signer": s2, "state": "pending"},
        {"signer": s3, "state": "readonly"},
    ])
    ll = loglistlib.parse_log_list(doc)
    assert len(ll) == 1
    assert ll.route(s3.log_id, 0).known
    assert not ll.route(s1.log_id, 0).known
    assert not ll.route(s2.log_id, 0).known


def test_spki_codec_roundtrip_and_rejection():
    for curve in (vhost.P256, vhost.P384):
        s = sctlib.EcSctSigner(f"audit-spki-{curve.name}", curve)
        spki = loglistlib.spki_from_signer(s)
        key = loglistlib.parse_spki(spki)
        assert key["alg"] == curve.name
        assert int(key["x"], 16) == s.q[0]
        assert int(key["y"], 16) == s.q[1]
    r = sctlib.RsaSctSigner()
    key = loglistlib.parse_spki(loglistlib.spki_from_signer(r))
    assert key == {"alg": "rsa", "n": hex(r.n), "e": hex(r.e)}
    with pytest.raises(ValueError, match="algorithm OID"):
        # Ed25519 OID — present in the wild, not in the CT ecosystem.
        loglistlib.parse_spki(bytes.fromhex(
            "302a300506032b6570032100") + bytes(32))
    with pytest.raises(ValueError):
        loglistlib.parse_spki(b"\x30\x03\x02\x01\x01")


# -- quarantine lane ---------------------------------------------------------


def test_quarantine_spool_file_and_replay(tmp_path):
    spool = quarlib.QuarantineSpool(str(tmp_path / "spool"))
    a, b = b"\x30\x03\x02\x01\x01", b"\x30\x03\x02\x01\x02"
    spool.file(a, index=5, log_url="l", reasons=["digest"])
    spool.file(b, index=6, log_url="l", reasons=["ok", "r"])
    spool.file(a, index=7, log_url="l", reasons=["digest"])  # re-filed
    assert spool.count == 3
    recs = spool.replay()
    assert len(recs) == 2  # content-addressed: same DER, same file
    assert sorted(r["sha256"] for r in recs) == sorted(
        hashlib.sha256(x).hexdigest() for x in (a, b))
    assert set(spool.replay_ders()) == {a, b}
    for r in recs:
        assert r["format"] == quarlib.SPOOL_FORMAT
    # Unknown record formats refuse to replay.
    bad = tmp_path / "spool" / "zzzz.json"
    bad.write_text(json.dumps({"format": "NOPE", "der": ""}))
    with pytest.raises(ValueError, match="NOPE"):
        spool.replay()
    # In-memory posture: no directory, records still held and counted.
    mem = quarlib.QuarantineSpool("")
    mem.file(a, index=0)
    assert mem.count == 1 and mem.replay_ders() == [a]


def test_check_batch_unmeasured_without_native(monkeypatch):
    monkeypatch.setenv("CTMR_NATIVE", "0")
    data, length = _pack_rows([b"\x30\x00"])
    chk = quarlib.check_batch(data, length)
    assert not chk.measured and chk.count == 0


# -- recorded-shard driver ---------------------------------------------------

SMALL_KINDS = (
    ["p256_valid"] * 6 + ["p256_corrupt"] * 2 + ["p384_retired"] * 2
    + ["rsa"] * 2 + ["unknown_log"] * 2 + ["out_of_interval"] * 2
    + ["no_sct"] * 8
)

SMALL_EXPECT = {
    "entries": 24, "sct_lanes": 16, "no_sct": 8,
    "verified": 12, "failed": 2, "no_key": 2,
    "device_lanes": 12, "host_lanes": 2,
    "retired": 2, "out_of_interval": 2, "unknown_log": 2,
}


def _small_doc() -> dict:
    """A 24-entry single-page CTMRAU01 doc with every lane class —
    the cheap stand-in for the checked-in 1024-entry shard."""
    from ct_mapreduce_tpu.utils import minicert

    signers = fxlib.fixture_signers()
    issuers = [
        minicert.make_cert(serial=100 + i,
                           issuer_cn=f"Small Audit CA {i}",
                           is_ca=True, not_after=FUTURE)
        for i in range(2)
    ]
    entries = []
    for idx, kind in enumerate(SMALL_KINDS):
        issuer = issuers[idx % 2]
        base = minicert.make_cert(
            serial=9000 + idx, issuer_cn=f"Small Audit CA {idx % 2}",
            subject_cn=f"small-{idx}.example", is_ca=False,
            not_after=FUTURE)
        ts = fxlib.TS_IN_INTERVAL + idx
        if kind == "no_sct":
            der = base
        else:
            signer = {
                "p256_valid": signers["p256"],
                "p256_corrupt": signers["p256"],
                "out_of_interval": signers["p256"],
                "p384_retired": signers["p384"],
                "rsa": signers["rsa"],
                "unknown_log": signers["unknown"],
            }[kind]
            if kind == "out_of_interval":
                ts = fxlib.TS_OUTSIDE + idx
            der = sctlib.attach_sct(
                base, signer, ts,
                corrupt_signature=(kind == "p256_corrupt"),
                issuer_der=issuer)
        li = leaflib.encode_leaf_input(der, timestamp_ms=ts)
        ed = leaflib.encode_extra_data([issuer])
        entries.append({
            "leaf_input": base64.b64encode(li).decode(),
            "extra_data": base64.b64encode(ed).decode(),
        })
    return {
        "format": drvlib.RECORDED_FORMAT,
        "log_url": "https://small.audit.example/",
        "log_list": fxlib.fixture_log_list_doc(signers),
        "pages": [{"start": 0, "entries": entries}],
    }


def _small_driver(doc, quarantine_dir=""):
    # Default capacity + the CLI's --batch-size/--flush-size values so
    # every driver in this module (and the CLI test) shares ONE set of
    # compiled dispatch shapes.
    ll = loglistlib.parse_log_list(doc["log_list"])
    return drvlib.AuditDriver(
        ll, quarantine_dir=quarantine_dir,
        batch_size=16, flush_size=16, batch_width=32)


@pytest.fixture(scope="module")
def small_run():
    from ct_mapreduce_tpu.telemetry import metrics as tmetrics

    doc = _small_doc()
    sink = tmetrics.InMemSink()
    prev = tmetrics.get_sink()
    tmetrics.set_sink(sink)
    try:
        drv = _small_driver(doc)
        rep = drv.run_recorded(doc)
        snap = sink.snapshot()
    finally:
        tmetrics.set_sink(prev)
    return doc, drv, rep, snap


def test_driver_small_doc_tallies(small_run):
    _doc, _drv, rep, snap = small_run
    e = SMALL_EXPECT
    assert rep.entries == e["entries"]
    assert rep.sct_lanes == e["sct_lanes"]
    assert rep.no_sct == e["no_sct"]
    assert rep.verified == e["verified"]
    assert rep.failed == e["failed"]
    assert rep.verifier_no_key == e["no_key"]
    assert rep.device_lanes == e["device_lanes"]
    assert rep.host_lanes == e["host_lanes"]
    assert rep.retired == e["retired"]
    assert rep.out_of_interval == e["out_of_interval"]
    assert rep.unknown_log == e["unknown_log"]
    assert rep.quarantined == 0
    assert rep.decode_failed == 0
    if _native_sct_available():
        assert rep.divergence_measured
    # Per-issuer folds: two CAs, each with half the verifiable mass.
    assert len(rep.per_issuer) == 2
    assert sum(v for v, _ in rep.per_issuer.values()) == e["verified"]
    assert sum(f for _, f in rep.per_issuer.values()) == e["failed"]
    # Audit metrics really published.
    c = snap["counters"]
    assert c["audit.entries"] == float(e["entries"])
    assert c["audit.verified"] == float(e["verified"])
    assert c["audit.failed"] == float(e["failed"])
    assert c["audit.unknown_log"] == float(e["unknown_log"])
    assert c["audit.retired_sct"] == float(e["retired"])
    assert c["audit.out_of_interval"] == float(e["out_of_interval"])
    assert "audit.quarantined" not in c
    # Report serializes.
    j = rep.to_json()
    json.dumps(j)
    assert j["verified"] == e["verified"]
    assert len(j["perIssuer"]) == 2


def test_driver_tile_scaling():
    doc = _small_doc()
    drv = _small_driver(doc)
    rep = drv.run_recorded(doc, tile=3)
    e = SMALL_EXPECT
    assert rep.entries == 3 * e["entries"]
    assert rep.verified == 3 * e["verified"]
    assert rep.failed == 3 * e["failed"]
    assert rep.retired == 3 * e["retired"]
    assert rep.unknown_log == 3 * e["unknown_log"]
    assert sum(rep.per_log.values()) == 3 * e["sct_lanes"]
    assert sum(v for v, _ in rep.per_issuer.values()) == 3 * e["verified"]


def test_checked_in_shard_tallies_and_per_issuer_oracle():
    """The checked-in 1,024-entry shard through the whole audit path:
    every tally is the fixture's ground truth, nothing is quarantined
    on the real corpus (the native scanner and its mirror agree on
    every lane, and the divergence was measured where the scanner
    exists), and the per-issuer verified/failed folds equal a
    reference recomputed lane by lane on the host with the pure-python
    verifier."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "recorded_shard.json.gz")
    doc = drvlib.load_recorded(path)
    log_list = loglistlib.parse_log_list(doc["log_list"])
    drv = drvlib.AuditDriver(log_list, batch_size=16, flush_size=16,
                             batch_width=32)
    rep = drv.run_recorded(doc)

    want = fxlib.expected_tallies()
    got = {"entries": rep.entries, "sct_lanes": rep.sct_lanes,
           "no_sct": rep.no_sct, "verified": rep.verified,
           "failed": rep.failed, "no_key": rep.verifier_no_key,
           "device_lanes": rep.device_lanes, "host_lanes": rep.host_lanes,
           "retired": rep.retired, "out_of_interval": rep.out_of_interval,
           "unknown_log": rep.unknown_log}
    assert got == {name: want[name] for name in got}
    assert min(got.values()) > 0  # every lane class is in the corpus
    assert rep.quarantined == 0
    if _native_sct_available():
        assert rep.divergence_measured

    reg = log_list.registry()
    oracle: dict = {}
    for page in doc["pages"]:
        start = int(page.get("start", 0))
        for i, e in enumerate(page["entries"]):
            dec = leaflib.decode_json_entry(start + i, e)
            ikh = (sctlib.issuer_key_hash_of(dec.issuer_der)
                   if dec.issuer_der else sctlib.ZERO_IKH)
            status, sct, digest, _, _ = sctlib.extract_sct_lane(
                dec.cert_der, ikh)
            if status == sctlib.SCT_NONE or sct is None:
                continue
            key = reg.get(sct.log_id)
            if key is None:
                continue  # no_key lanes fold into no per-issuer row
            ok = sctlib.host_verify_sct(digest, sct, key)
            v, f = oracle.get(ikh, (0, 0))
            oracle[ikh] = (v + int(ok), f + int(not ok))
    assert len(rep.per_issuer) == 8
    assert sorted(rep.per_issuer.values()) == sorted(oracle.values())


def test_driver_emits_filter_artifact(tmp_path):
    """The last leg of the acceptance flow: decode → verify →
    aggregate → FILTER. A driver armed with ``filter_path`` captures
    every inserted serial and checkpoint-save compiles the versioned
    artifact; every audited serial queries positive in its (issuer,
    expDate) group (no false negatives by contract)."""
    from ct_mapreduce_tpu.filter import artifact as fartifact

    doc = _small_doc()
    ll = loglistlib.parse_log_list(doc["log_list"])
    fpath = str(tmp_path / "audited.filter")
    drv = drvlib.AuditDriver(
        ll, batch_size=16, flush_size=16, batch_width=32,
        filter_path=fpath)
    rep = drv.run_recorded(doc)
    assert rep.entries == SMALL_EXPECT["entries"]
    drv.aggregator.save_checkpoint(str(tmp_path / "audited.npz"))

    art = fartifact.read_artifact(fpath)
    # Every decoded entry was inserted (no CA/expiry drops in the
    # fixture), so the artifact covers all 24 serials in 2 groups.
    assert art.n_serials == SMALL_EXPECT["entries"]
    assert len({iss for iss, _ in art.groups}) == 2

    reg = drv.aggregator.registry
    cap = drv.aggregator.filter_capture
    assert cap is not None and sum(len(s) for s in cap.values()) == 24
    for (idx, eh), serials in sorted(cap.items()):
        iss = reg.issuer_at(idx).id()
        for serial in sorted(serials):
            assert art.query(iss, eh, serial), (iss, eh, serial.hex())
    # An absent serial resolves negative in this (deterministic)
    # build — unknown serials only FP at the target rate.
    some_idx, some_eh = sorted(cap)[0]
    assert not art.query(reg.issuer_at(some_idx).id(), some_eh,
                         b"\x99" * 9)


@needs_native
def test_quarantine_exclusion_property(tmp_path, monkeypatch):
    """The acceptance property: a diverging lane is spooled and the
    aggregate outcome is IDENTICAL to a run where that entry never
    existed — quarantine is exclusion, never a third verdict."""
    from ct_mapreduce_tpu.native import leafpack
    from ct_mapreduce_tpu.telemetry import metrics as tmetrics

    doc = _small_doc()
    n = len(doc["pages"][0]["entries"])
    target = 0  # a p256_valid lane

    real = leafpack.extract_scts
    fired = {"n": 0}

    def tampered(data, length, issuer_key_hash=None, **kw):
        out = real(data, length, issuer_key_hash=issuer_key_hash, **kw)
        # Only the pre-pass batch (full page width) is tampered, and
        # only once — the sink's own extraction stays honest.
        if fired["n"] == 0 and out.ok.shape[0] == n:
            fired["n"] = 1
            out.timestamp_ms = np.array(out.timestamp_ms, copy=True)
            out.timestamp_ms[target] += 1
        return out

    monkeypatch.setattr(leafpack, "extract_scts", tampered)
    qdir = str(tmp_path / "spool")
    sink = tmetrics.InMemSink()
    prev = tmetrics.get_sink()
    tmetrics.set_sink(sink)
    try:
        drv = _small_driver(doc, quarantine_dir=qdir)
        rep = drv.run_recorded(doc)
        snap = sink.snapshot()
    finally:
        tmetrics.set_sink(prev)
    assert fired["n"] == 1
    assert rep.quarantined == 1 and rep.divergence_measured
    assert snap["counters"]["audit.quarantined"] == 1.0
    assert rep.entries == SMALL_EXPECT["entries"] - 1
    # The spool holds the offending DER with the disagreeing field.
    recs = drv.spool.replay()
    assert len(recs) == 1
    assert recs[0]["reasons"] == ["timestamp_ms"]
    assert recs[0]["index"] == target
    dec = leaflib.decode_json_entry(
        target, doc["pages"][0]["entries"][target])
    assert drv.spool.replay_ders() == [dec.cert_der]

    # Control: the same doc with the entry REMOVED, no tamper.
    monkeypatch.setattr(leafpack, "extract_scts", real)
    doc2 = _small_doc()
    del doc2["pages"][0]["entries"][target]
    drv2 = _small_driver(doc2)
    rep2 = drv2.run_recorded(doc2)
    assert rep2.quarantined == 0
    for f in ("verified", "failed", "verifier_no_key", "device_lanes",
              "host_lanes", "entries", "no_sct"):
        assert getattr(rep, f) == getattr(rep2, f), f
    assert sorted(rep.per_issuer.values()) \
        == sorted(rep2.per_issuer.values())


def test_driver_feeds_statistics_serve_and_checkpoint(
        small_run, tmp_path):
    """The audit aggregate flows through every EXISTING surface: the
    storage_statistics text + JSON totals, the serve plane's /issuer
    meta, and checkpoint round-trips — no parallel bookkeeping."""
    import io

    from ct_mapreduce_tpu.agg.aggregator import HostSnapshotAggregator
    from ct_mapreduce_tpu.cmd import storage_statistics as stats
    from ct_mapreduce_tpu.config import CTConfig
    from ct_mapreduce_tpu.serve.server import MembershipOracle

    _doc, drv, rep, _snap = small_run
    agg = drv.aggregator
    path = str(tmp_path / "audit-agg.npz")
    agg.save_checkpoint(path)

    cfg = CTConfig()
    cfg.backend = "tpu"
    cfg.agg_state_path = path
    out = io.StringIO()
    assert stats.report_from_tpu_snapshot(cfg, out) == 0
    text = out.getvalue()
    assert f"{rep.verified} scts verified" in text
    assert f"{rep.failed} scts failed" in text
    report = stats.collect_tpu_report(cfg)
    assert report["totals"]["sctsVerified"] == rep.verified
    assert report["totals"]["sctsFailed"] == rep.failed

    h = HostSnapshotAggregator(capacity=1 << 10)
    h.load_checkpoint(path)
    assert h.verify_counts() == rep.per_issuer

    oracle = MembershipOracle(agg, replicas=1, device=False,
                              cache_size=-1)
    try:
        total_v = total_f = 0
        for iss_id in rep.per_issuer:
            meta = oracle.issuer_meta(iss_id)
            total_v += meta["verified"]
            total_f += meta["failed"]
        assert (total_v, total_f) == (rep.verified, rep.failed)
    finally:
        oracle.close()


def test_resolve_audit_knob_ladder(monkeypatch):
    from ct_mapreduce_tpu import audit as auditpkg

    for var in ("CTMR_AUDIT_LOG_LIST", "CTMR_AUDIT_QUARANTINE_DIR"):
        monkeypatch.delenv(var, raising=False)
    assert auditpkg.resolve_audit() == ("", "")
    monkeypatch.setenv("CTMR_AUDIT_LOG_LIST", "/tmp/list.json")
    monkeypatch.setenv("CTMR_AUDIT_QUARANTINE_DIR", "/tmp/spool")
    assert auditpkg.resolve_audit() == ("/tmp/list.json", "/tmp/spool")
    # Explicit beats env; an EMPTY explicit is "unset" on the ladder
    # (is_set = nonempty_str), so the spool knob still reads the env.
    assert auditpkg.resolve_audit("x.json", "") \
        == ("x.json", "/tmp/spool")
    assert auditpkg.resolve_audit("x.json", "/spool2") \
        == ("x.json", "/spool2")
    with pytest.raises(ValueError, match="log list"):
        monkeypatch.delenv("CTMR_AUDIT_LOG_LIST")
        drvlib.load_driver()


def test_audit_cli_recorded_json(tmp_path, capsys, monkeypatch):
    from tools import audit as audit_cli

    monkeypatch.delenv("CTMR_AUDIT_LOG_LIST", raising=False)
    monkeypatch.delenv("CTMR_AUDIT_QUARANTINE_DIR", raising=False)
    # Pin the verifier to the suite's shared compiled width — the CLI
    # builds its sink through the env ladder, and a fresh width would
    # cost a whole extra kernel compile inside the tier-1 budget.
    monkeypatch.setenv("CTMR_VERIFY_BATCH", "32")
    doc = _small_doc()
    path = str(tmp_path / "small.json.gz")
    drvlib.write_recorded(path, doc)
    rc = audit_cli.main(["--recorded", path, "--json",
                         "--flush-size", "16", "--batch-size", "16"])
    captured = capsys.readouterr()
    rep = json.loads(captured.out)
    assert rc == 0  # nothing quarantined
    assert rep["entries"] == SMALL_EXPECT["entries"]
    assert rep["verified"] == SMALL_EXPECT["verified"]
    assert rep["failed"] == SMALL_EXPECT["failed"]
    assert len(rep["perIssuer"]) == 2
    # Human-readable mode renders without crashing.
    rc = audit_cli.main(["--recorded", path])
    assert rc == 0
    assert "per-issuer" in capsys.readouterr().out


def test_recorded_format_rejected_loudly(tmp_path):
    path = str(tmp_path / "bad.json.gz")
    drvlib.write_recorded(path, {"pages": []})
    good = drvlib.load_recorded(path)
    assert good["format"] == drvlib.RECORDED_FORMAT
    import gzip

    with gzip.open(path, "wt", encoding="utf-8") as fh:
        json.dump({"format": "CTMRXX99", "pages": []}, fh)
    with pytest.raises(ValueError, match="CTMRXX99"):
        drvlib.load_recorded(path)


def test_checked_in_shard_matches_generator():
    """The checked-in corpus is EXACTLY what the generator emits —
    byte-stable regeneration is the tamper/drift guard for a fixture
    that test gates trust."""
    root = os.path.join(os.path.dirname(__file__), "..")
    path = os.path.join(root, "tests", "data",
                        "recorded_shard.json.gz")
    doc = drvlib.load_recorded(path)
    assert doc["mix"] == dict(fxlib.MIX, no_sct=816)
    n = sum(len(p["entries"]) for p in doc["pages"])
    assert n == fxlib.PAGE_SIZE * fxlib.N_PAGES == 1024
    # The embedded list is the fixture signers' production publication.
    ll = loglistlib.parse_log_list(doc["log_list"])
    signers = fxlib.fixture_signers()
    assert set(ll.shards) == {signers["p256"].log_id,
                              signers["p384"].log_id,
                              signers["rsa"].log_id}
