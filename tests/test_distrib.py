"""Filter distribution plane (round 18): epoch deltas, upstream
containers, CDN-grade serving.

Pins the acceptance contract of ISSUE 13:
- any epoch sequence's delta chain replays to bytes IDENTICAL to the
  full build — including across a table-growth event and a fleet
  merge — with truncated/corrupted/misordered links rejected loudly
  through the mandatory per-link SHA-256 checks;
- container encodings (mlbf, clubcard) answer every membership
  question exactly as the source artifact does, deterministically;
- the distribution store bounds chain length with full-snapshot
  anchors, evicts history, and ranks fleet-merged publishes above
  local builds;
- the HTTP tier: strong ETags, If-None-Match ⇒ 304, Accept-Encoding
  negotiation against pre-compressed caches, delta/manifest/container
  routes, and byte-identical serving across a 2-worker pair;
- platformProfile: one data file feeds every subsystem's knob ladder
  (explicit > env > profile > default).
"""

import gzip
import json
import os
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ct_mapreduce_tpu.agg.aggregator import TpuAggregator  # noqa: E402
from ct_mapreduce_tpu.distrib import (  # noqa: E402
    ChainManifest,
    DeltaError,
    FilterDistributor,
    apply_chain,
    apply_delta,
    compute_delta,
    decode_container,
    encode_container,
    negotiate_encoding,
    resolve_distrib,
    split_bundle,
)
from ct_mapreduce_tpu.distrib import delta as delta_mod  # noqa: E402
from ct_mapreduce_tpu.distrib.container import ContainerError  # noqa: E402
from ct_mapreduce_tpu.filter import (  # noqa: E402
    FilterArtifact,
    build_artifact,
    build_from_aggregator,
)
from ct_mapreduce_tpu.utils import minicert  # noqa: E402

ISSUER_DER = minicert.make_cert(serial=1, issuer_cn="Distrib CA",
                                is_ca=True)
ISSUER_DER_B = minicert.make_cert(serial=2, issuer_cn="Distrib CA B",
                                  is_ca=True)


def corpus(n=60, issuer_cn="Distrib CA", issuer=ISSUER_DER, base=1000):
    return [
        (minicert.make_cert(serial=base + s, issuer_cn=issuer_cn,
                            subject_cn=f"d{s}.example"), issuer)
        for s in range(n)
    ]


def epoch_sets(rng, n_groups, per_group, salt):
    return {
        (f"issuer-{g}", 500_000 + 24 * g): {
            bytes([salt, g, s % 251, 7]) + bytes([int(x) for x in
                                                  rng.integers(0, 256, 2)])
            for s in range(per_group)
        }
        for g in range(n_groups)
    }


def build(sets):
    return build_artifact(sets, fp_rate=0.01, use_device=False).to_bytes()


# -- delta chain replay == full build (property) --------------------------


def test_delta_chain_replays_any_epoch_sequence():
    """Randomized epoch sequences — serials added, groups added,
    groups removed — always replay through the delta chain to bytes
    identical to the full build at every step."""
    rng = np.random.default_rng(20260805)
    for seq in range(3):
        sets = epoch_sets(rng, n_groups=6, per_group=25, salt=seq)
        blobs = [build(sets)]
        for _ in range(4):
            # Mutate: grow a couple of groups, sometimes add/remove one.
            for key in sorted(sets)[:2]:
                sets[key] = set(sets[key]) | {
                    bytes([int(x) for x in rng.integers(0, 256, 5)])
                    for _ in range(int(rng.integers(1, 6)))}
            if rng.integers(2):
                sets[(f"new-{seq}-{len(blobs)}", 700_000)] = {
                    bytes([int(x) for x in rng.integers(0, 256, 4)])}
            if rng.integers(2) and len(sets) > 3:
                del sets[sorted(sets)[-1]]
            blobs.append(build(sets))
        deltas = [compute_delta(blobs[i], blobs[i + 1], i, i + 1)
                  for i in range(len(blobs) - 1)]
        assert apply_chain(blobs[0], deltas) == blobs[-1]
        # And every intermediate prefix replays exactly too.
        for i in range(1, len(blobs)):
            assert apply_chain(blobs[0], deltas[:i]) == blobs[i]


def test_delta_chain_across_growth_and_fleet_merge(tmp_path):
    """The production epoch shapes: epoch 0 → 1 spans a table
    grow-and-rehash; epoch 1 → 2 lands on a MERGED fleet artifact
    (two worker checkpoints folded). The chain still replays to the
    exact merged-build bytes."""
    from ct_mapreduce_tpu.agg import merge
    from ct_mapreduce_tpu.filter import build_from_merged

    agg = TpuAggregator(capacity=1 << 8, batch_size=64, grow_at=0.5,
                        max_capacity=1 << 14)
    agg.enable_filter_capture()
    agg.ingest(corpus(n=40, base=1000))
    e0 = build_from_aggregator(agg, fp_rate=0.01).to_bytes()
    agg.ingest(corpus(n=120, base=3000))  # drives growth past 2^8
    assert agg.capacity > (1 << 8), "growth never fired"
    e1 = build_from_aggregator(agg, fp_rate=0.01).to_bytes()

    # Epoch 2: this worker + a second worker's disjoint half, merged.
    p0 = str(tmp_path / "agg.w0.npz")
    agg.save_checkpoint(p0)
    other = TpuAggregator(capacity=1 << 10, batch_size=64)
    other.enable_filter_capture()
    other.ingest(corpus(n=50, issuer_cn="Distrib CA B",
                        issuer=ISSUER_DER_B, base=9000))
    p1 = str(tmp_path / "agg.w1.npz")
    other.save_checkpoint(p1)
    e2 = build_from_merged(merge.load_checkpoints([p0, p1]),
                           fp_rate=0.01).to_bytes()

    d01 = compute_delta(e0, e1, 0, 1)
    d12 = compute_delta(e1, e2, 1, 2)
    assert apply_chain(e0, [d01, d12]) == e2
    assert apply_delta(apply_delta(e0, d01), d12) == e2


def test_delta_rejects_corruption_and_misorder():
    rng = np.random.default_rng(7)
    s0 = epoch_sets(rng, 4, 20, salt=1)
    s1 = {k: set(v) | {b"\x01\x02\x03"} for k, v in s0.items()}
    b0, b1 = build(s0), build(s1)
    d = compute_delta(b0, b1, 0, 1)
    assert apply_delta(b0, d) == b1
    # Corrupted payload byte: the target-hash check trips.
    corrupt = bytearray(d)
    corrupt[-3] ^= 0x40
    with pytest.raises(DeltaError):
        apply_delta(b0, bytes(corrupt))
    # Truncated link: payloadBytes no longer matches.
    with pytest.raises(DeltaError):
        apply_delta(b0, d[:-5])
    # Wrong base (misordered chain): the base-hash check trips.
    with pytest.raises(DeltaError, match="base mismatch"):
        apply_delta(b1, d)
    # Garbage magic.
    with pytest.raises(DeltaError, match="magic"):
        apply_delta(b0, b"XXXXXXXX" + d[8:])


def test_chain_manifest_validates_links():
    rng = np.random.default_rng(11)
    sets = epoch_sets(rng, 3, 15, salt=2)
    blobs = [build(sets)]
    for i in range(3):
        sets[sorted(sets)[0]] = set(sets[sorted(sets)[0]]) | {bytes([i, 9])}
        blobs.append(build(sets))
    links, dblobs = [], []
    for i in range(3):
        db = compute_delta(blobs[i], blobs[i + 1], i, i + 1)
        dblobs.append(db)
        import hashlib

        links.append(delta_mod.ChainLink(
            from_epoch=i, to_epoch=i + 1,
            sha256=hashlib.sha256(db).hexdigest(),
            base_sha256=delta_mod.artifact_sha256(blobs[i]),
            target_sha256=delta_mod.artifact_sha256(blobs[i + 1]),
            n_bytes=len(db)))
    man = ChainManifest(latest_epoch=3,
                        latest_sha256=delta_mod.artifact_sha256(blobs[3]),
                        latest_bytes=len(blobs[3]), anchors=[0],
                        links=links)
    # JSON round trip preserves the manifest.
    back = ChainManifest.from_json(man.to_json())
    assert back.to_json() == man.to_json()
    # A valid chain validates; replay confirms.
    path = man.validate_chain(0, 3, dblobs)
    assert [li.from_epoch for li in path] == [0, 1, 2]
    assert apply_chain(blobs[0], dblobs) == blobs[3]
    # Corrupted download: rejected BEFORE replay.
    bad = dblobs[:1] + [dblobs[1][:-1] + b"\x00"] + dblobs[2:]
    with pytest.raises(DeltaError, match="hash mismatch"):
        man.validate_chain(0, 3, bad)
    # Wrong blob count (truncated chain).
    with pytest.raises(DeltaError, match="length mismatch"):
        man.validate_chain(0, 3, dblobs[:2])
    # No path outside the manifest's span.
    with pytest.raises(DeltaError, match="no delta path"):
        man.validate_chain(5, 9, [])
    assert man.link_path(2, 1) is None


def test_split_bundle_roundtrip():
    rng = np.random.default_rng(13)
    sets = epoch_sets(rng, 3, 10, salt=3)
    b0 = build(sets)
    sets[sorted(sets)[0]] = set(sets[sorted(sets)[0]]) | {b"\xaa"}
    b1 = build(sets)
    sets[sorted(sets)[1]] = set(sets[sorted(sets)[1]]) | {b"\xbb"}
    b2 = build(sets)
    d1 = compute_delta(b0, b1, 10, 11)
    d2 = compute_delta(b1, b2, 11, 12)
    assert split_bundle(d1 + d2) == [d1, d2]
    assert apply_chain(b0, split_bundle(d1 + d2)) == b2
    with pytest.raises(DeltaError):
        split_bundle(d1 + b"junk")


# -- containers -----------------------------------------------------------


def test_container_query_parity_and_determinism():
    """Both container encodings answer exactly what the source
    artifact answers — for every known serial AND for random probes
    (FP pattern included) — and encode deterministically."""
    agg = TpuAggregator(capacity=1 << 10, batch_size=64)
    agg.enable_filter_capture()
    agg.ingest(corpus(n=80, base=1000))
    art = build_from_aggregator(agg, fp_rate=0.01)
    blob = art.to_bytes()
    rng = np.random.default_rng(20260805)
    probes = [rng.integers(0, 256, 5, dtype=np.uint8).tobytes()
              for _ in range(150)]
    for kind in ("mlbf", "clubcard"):
        cb = encode_container(art, kind)
        assert encode_container(FilterArtifact.from_bytes(blob),
                                kind) == cb  # deterministic
        back = decode_container(cb)
        for (idx, eh), serials in sorted(agg.filter_capture.items()):
            iss = agg.registry.issuer_at(idx).id()
            for sb in sorted(serials)[:30]:
                assert back.query(iss, eh, sb)
            for p in probes:
                assert back.query(iss, eh, p) == art.query(iss, eh, p)
            # Cross-bucket exactness survives the container.
            sb = sorted(serials)[0]
            assert back.query(iss, eh + 24, sb) \
                == art.query(iss, eh + 24, sb)


def test_container_error_paths():
    with pytest.raises(ContainerError, match="magic"):
        decode_container(b"NOTAMAGICblahblah")
    art = build_artifact({("i", 1): {b"\x01"}}, 0.01, use_device=False)
    mlbf = encode_container(art, "mlbf")
    with pytest.raises(ContainerError):
        decode_container(mlbf[:-3])  # truncated
    with pytest.raises(ContainerError, match="kind"):
        encode_container(art, "bloom3000")


# -- the distributor ------------------------------------------------------


def epoch_blobs(n, rng=None, groups=8, per=20):
    rng = rng or np.random.default_rng(99)
    sets = epoch_sets(rng, groups, per, salt=9)
    out = [build(sets)]
    for i in range(n - 1):
        key = sorted(sets)[i % len(sets)]
        sets[key] = set(sets[key]) | {bytes([i, 77, j]) for j in range(3)}
        out.append(build(sets))
    return out


def test_distributor_chain_anchors_and_eviction():
    blobs = epoch_blobs(8)
    d = FilterDistributor(history=4, max_chain=2)
    for e, blob in enumerate(blobs):
        assert d.publish(e, blob)
    man = d.manifest()
    # History bound: only the newest 4 epochs held.
    assert man["epochsHeld"] == [4, 5, 6, 7]
    assert d.latest().epoch == 7 and d.latest().blob == blobs[7]
    # Anchors: every (max_chain+1)th epoch forces a full snapshot;
    # no delta bundle crosses one.
    assert man["maxDeltaChain"] == 2
    links = {(li["fromEpoch"], li["toEpoch"]) for li in man["links"]}
    for from_e, to_e in links:
        assert to_e == from_e + 1
    # A surviving adjacent pair replays exactly.
    replayable = [(f, t) for f, t in sorted(links) if f >= 4]
    assert replayable, links
    f, t = replayable[0]
    bundle = d.delta_bundle(f, t)
    assert bundle is not None
    assert apply_chain(blobs[f], split_bundle(bundle)) == blobs[t]
    # Evicted epoch: no chain.
    assert d.delta_bundle(0, 7) is None
    # Stale publish ignored.
    assert not d.publish(3, blobs[3])


def test_distributor_source_ranking():
    blobs = epoch_blobs(4)
    d = FilterDistributor()
    assert d.publish(100, blobs[0], source="local")
    assert d.publish(101, blobs[1], source="local")
    # Fleet takes over: its own epoch space, store restarts clean.
    assert d.publish(1, blobs[2], source="fleet")
    assert d.latest().epoch == 1 and d.latest().blob == blobs[2]
    # Local can no longer override the merged artifact.
    assert not d.publish(102, blobs[3], source="local")
    assert d.latest().blob == blobs[2]
    assert d.publish(2, blobs[3], source="fleet")
    assert d.latest().epoch == 2


def test_negotiate_encoding():
    from ct_mapreduce_tpu.distrib import zstd_available

    assert negotiate_encoding("gzip") == "gzip"
    assert negotiate_encoding("gzip;q=0") is None
    assert negotiate_encoding("") is None
    assert negotiate_encoding("identity") is None
    assert negotiate_encoding("br, gzip;q=0.5") == "gzip"
    if zstd_available():
        assert negotiate_encoding("zstd, gzip") == "zstd"
    else:
        assert negotiate_encoding("zstd") is None
        assert negotiate_encoding("zstd, gzip") == "gzip"
    # Wildcard accepts whatever the build offers.
    assert negotiate_encoding("*") in ("gzip", "zstd")


# -- HTTP tier ------------------------------------------------------------


@pytest.fixture
def served_pair():
    """Two QueryServers ('workers') whose distribution stores are fed
    the SAME artifact bytes — the fleet serving shape."""
    from ct_mapreduce_tpu.serve.server import QueryServer

    agg = TpuAggregator(capacity=1 << 10, batch_size=64)
    agg.enable_filter_capture()
    agg.ingest(corpus(n=70, base=1000))
    servers = [QueryServer(agg, 0, filter_first=True).start()
               for _ in range(2)]
    e0 = [s.oracle.distributor.latest().blob for s in servers]
    assert e0[0] == e0[1]  # deterministic build == same bytes
    agg.ingest(corpus(n=30, base=7000))
    blob1 = build_from_aggregator(
        agg, fp_rate=servers[0].oracle.filter_fp_rate).to_bytes()
    for s in servers:
        latest = s.oracle.distributor.latest().epoch
        assert s.oracle.distributor.publish(latest + 1, blob1,
                                            source="local")
    try:
        yield servers, e0[0], blob1
    finally:
        for s in servers:
            s.stop()


def _get(url, headers=None):
    req = urllib.request.Request(url, headers=headers or {})
    return urllib.request.urlopen(req)


def test_http_etag_304_encoding_delta_and_worker_parity(served_pair):
    servers, blob0, blob1 = served_pair
    bases = [f"http://127.0.0.1:{s.port}" for s in servers]

    # Every worker serves byte-identical artifacts with identical
    # strong ETags — full, containers, manifest.
    full, etags = [], []
    for base in bases:
        r = _get(base + "/filter")
        full.append(r.read())
        etags.append(r.headers["ETag"])
        assert r.headers["Cache-Control"].startswith("public")
        assert r.headers["Last-Modified"]
        assert r.headers["Vary"] == "Accept-Encoding"
    assert full[0] == full[1] == blob1
    assert etags[0] == etags[1]
    for kind in ("mlbf", "clubcard"):
        payloads = [_get(f"{b}/filter/container/{kind}").read()
                    for b in bases]
        assert payloads[0] == payloads[1]
        assert decode_container(payloads[0]).n_serials == 100

    # Conditional GET: warm client pays zero body bytes, from EITHER
    # worker (the ETag is fleet-global).
    for base in bases:
        with pytest.raises(urllib.error.HTTPError) as err:
            _get(base + "/filter", {"If-None-Match": etags[0]})
        assert err.value.code == 304
        assert err.value.read() == b""
        assert err.value.headers["ETag"] == etags[0]
    # A stale ETag still gets the full body.
    r = _get(bases[0] + "/filter", {"If-None-Match": '"deadbeef"'})
    assert r.read() == blob1

    # Content negotiation: gzip round-trips to the identity bytes and
    # repeated requests hit the pre-compressed cache (same payload).
    r = _get(bases[0] + "/filter", {"Accept-Encoding": "gzip"})
    assert r.headers["Content-Encoding"] == "gzip"
    gz = r.read()
    assert gzip.decompress(gz) == blob1
    r2 = _get(bases[0] + "/filter", {"Accept-Encoding": "gzip;q=1.0"})
    assert r2.read() == gz
    # identity-only clients get identity.
    r3 = _get(bases[0] + "/filter", {"Accept-Encoding": "identity"})
    assert "Content-Encoding" not in r3.headers
    assert r3.read() == blob1

    # Delta route: a lagging client replays to the exact full bytes.
    man = json.loads(_get(bases[0] + "/filter/manifest").read())
    from_e, to_e = man["latestEpoch"] - 1, man["latestEpoch"]
    bundles = [_get(f"{b}/filter/delta/{from_e}/{to_e}").read()
               for b in bases]
    assert bundles[0] == bundles[1]
    links = split_bundle(bundles[0])
    ChainManifest.from_json(man).validate_chain(from_e, to_e, links)
    assert apply_chain(blob0, links) == blob1
    r = _get(f"{bases[0]}/filter/delta/{from_e}/{to_e}")
    assert "immutable" in r.headers["Cache-Control"]
    # Unknown spans 404 (client falls back to full-pull).
    with pytest.raises(urllib.error.HTTPError) as err:
        _get(f"{bases[0]}/filter/delta/998/999")
    assert err.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as err:
        _get(f"{bases[0]}/filter/container/nope")
    assert err.value.code == 404
    # Manifest reports the serving inventory (fl02 default build →
    # the rev-2 delta wire).
    assert man["format"] == "CTMRDL02"
    assert man["containers"] == ["clubcard", "mlbf"]
    assert "gzip" in man["encodings"]
    # /healthz carries the distribution stats.
    stats = servers[0].oracle.stats()
    assert stats["distrib_latest_epoch"] == to_e
    assert stats["distrib_links"] >= 1


def test_publish_artifact_fleet_source_via_oracle():
    """The ct-fetch fan-out path: externally built (merged) bytes
    publish through the oracle and outrank the local build."""
    from ct_mapreduce_tpu.serve.server import MembershipOracle

    agg = TpuAggregator(capacity=1 << 10, batch_size=64)
    agg.enable_filter_capture()
    agg.ingest(corpus(n=30, base=1000))
    oracle = MembershipOracle(agg, filter_first=True,
                              max_delay_s=0.001)
    try:
        assert oracle.distributor.latest() is not None  # local build
        merged = build_from_aggregator(agg, fp_rate=0.02).to_bytes()
        assert oracle.publish_artifact(3, merged)
        assert oracle.distributor.latest().epoch == 3
        assert oracle.distributor.latest().blob == merged
        # A later local refresh cannot displace the fleet artifact.
        oracle.refresh_filter()
        assert oracle.distributor.latest().blob == merged
    finally:
        oracle.close()


# -- CLI ------------------------------------------------------------------


def test_ct_filter_cli_delta_apply_container(tmp_path):
    import io

    from ct_mapreduce_tpu.cmd import ct_filter

    rng = np.random.default_rng(17)
    s0 = epoch_sets(rng, 4, 15, salt=5)
    b0 = build(s0)
    s1 = {k: set(v) | {b"\x42\x42"} for k, v in s0.items()}
    b1 = build(s1)
    p0, p1 = str(tmp_path / "e0.filter"), str(tmp_path / "e1.filter")
    open(p0, "wb").write(b0)
    open(p1, "wb").write(b1)

    dpath = str(tmp_path / "e0-e1.delta")
    buf = io.StringIO()
    rc = ct_filter.main(["delta", "-base", p0, "-target", p1,
                         "-out", dpath, "-fromEpoch", "0",
                         "-toEpoch", "1"], out=buf)
    assert rc == 0
    meta = json.loads(buf.getvalue())
    assert meta["bytes"] == os.path.getsize(dpath)

    rpath = str(tmp_path / "replayed.filter")
    buf = io.StringIO()
    assert ct_filter.main(["apply", "-base", p0, "-delta", dpath,
                           "-out", rpath], out=buf) == 0
    assert open(rpath, "rb").read() == b1
    # A corrupted link exits 2, not a traceback.
    bad = str(tmp_path / "bad.delta")
    blob = bytearray(open(dpath, "rb").read())
    blob[-1] ^= 0xFF
    open(bad, "wb").write(bytes(blob))
    assert ct_filter.main(["apply", "-base", p0, "-delta", bad,
                           "-out", str(tmp_path / "x.filter")],
                          out=io.StringIO()) == 2

    for kind in ("mlbf", "clubcard"):
        cpath = str(tmp_path / f"run.{kind}")
        buf = io.StringIO()
        assert ct_filter.main(["container", "-artifact", p1,
                               "-kind", kind, "-out", cpath],
                              out=buf) == 0
        back = decode_container(open(cpath, "rb").read())
        assert back.n_serials == json.loads(buf.getvalue())["serials"]


# -- config surface -------------------------------------------------------


def test_resolve_distrib_layering(monkeypatch, tmp_path):
    monkeypatch.delenv("CTMR_DISTRIB_HISTORY", raising=False)
    monkeypatch.delenv("CTMR_MAX_DELTA_CHAIN", raising=False)
    monkeypatch.delenv("CTMR_PLATFORM_PROFILE", raising=False)
    assert resolve_distrib() == (8, 4)
    monkeypatch.setenv("CTMR_DISTRIB_HISTORY", "16")
    monkeypatch.setenv("CTMR_MAX_DELTA_CHAIN", "6")
    assert resolve_distrib() == (16, 6)
    # Explicit beats env.
    assert resolve_distrib(history=3, max_chain=2) == (3, 2)
    # Unparseable env falls through.
    monkeypatch.setenv("CTMR_DISTRIB_HISTORY", "lots")
    assert resolve_distrib()[0] == 8
    # Profile sits under env, above defaults.
    prof = tmp_path / "prof.json"
    prof.write_text(json.dumps({
        "version": 1, "platform": "test",
        "knobs": {"distrib": {"distribHistory": 12,
                              "maxDeltaChain": 9}}}))
    monkeypatch.setenv("CTMR_PLATFORM_PROFILE", str(prof))
    monkeypatch.delenv("CTMR_DISTRIB_HISTORY", raising=False)
    monkeypatch.delenv("CTMR_MAX_DELTA_CHAIN", raising=False)
    assert resolve_distrib() == (12, 9)
    monkeypatch.setenv("CTMR_MAX_DELTA_CHAIN", "5")
    assert resolve_distrib() == (12, 5)  # env beats profile
    assert resolve_distrib(max_chain=2) == (12, 2)  # explicit beats all


# -- zstd wire leg (ROADMAP 4(c): validated where the module exists) ------


def test_zstd_encoding_leg(served_pair):
    """Gated on the optional `zstandard` module (absent in the default
    CI image — skips cleanly there; ROADMAP 4(c) asks for validation
    on a host that has it): the fleet advertises zstd, serves full and
    delta pulls with Content-Encoding: zstd whose bodies decompress to
    the exact deterministic bytes, and the pre-compressed cache bytes
    are themselves deterministic across workers."""
    zstandard = pytest.importorskip("zstandard")
    from ct_mapreduce_tpu.distrib import zstd_available

    assert zstd_available()
    servers, blob0, blob1 = served_pair
    wire = []
    for s in servers:
        base = f"http://127.0.0.1:{s.port}"
        man = json.loads(_get(base + "/filter/manifest").read())
        assert "zstd" in man["encodings"]
        r = _get(base + "/filter",
                 headers={"Accept-Encoding": "zstd, gzip"})
        assert r.headers.get("Content-Encoding") == "zstd"
        body = r.read()
        assert zstandard.ZstdDecompressor().decompress(body) == blob1
        wire.append(body)
        latest = man["latestEpoch"]
        rd = _get(f"{base}/filter/delta/{latest - 1}/{latest}",
                  headers={"Accept-Encoding": "zstd"})
        if rd.headers.get("Content-Encoding") == "zstd":
            bundle = zstandard.ZstdDecompressor().decompress(rd.read())
        else:  # tiny deltas may not pay for compression
            bundle = rd.read()
        links = split_bundle(bundle)
        assert apply_chain(blob0, links) == blob1
    # Deterministic compressed bytes (gzip mtime=0 discipline applies
    # to zstd too): any worker's wire bytes are authoritative.
    assert wire[0] == wire[1]


def test_pull_storm_over_two_workers_moves_deltas_not_artifacts():
    """A small client storm against two serving workers, counted in
    pulls and bytes (no clock): the workers serve identical bytes
    (``run_storm`` raises before the storm otherwise, and validates
    sampled delta chains against the full artifact), every pull class
    occurs, warm clients revalidate with 304s, and the clients that
    held an earlier epoch fetch under a fifth of what full pulls would
    have cost them."""
    from tools import pullstorm

    report = pullstorm.run_storm(
        clients=200, epochs=4, groups=24, per_group=30, churn=2,
        workers=2, threads=8, validate_every=10)
    assert report["worker_parity"] == 1 and report["workers"] == 2
    for kind in ("304", "delta", "full"):
        assert report["pulls"][kind]["count"] > 0, report["pulls"]
    assert report["ratio_304"] > 0.1
    assert report["delta_304_vs_full"] < 0.20
    assert report["wire_vs_counterfactual"] < 0.5


def test_pullstorm_force_zstd_flag():
    """`tools/pullstorm.py --force-zstd` drives every compressible
    pull through zstd end to end (skips without the module; the flag
    itself must fail loudly in that case — asserted in the else arm)."""
    from tools import pullstorm

    try:
        import zstandard  # noqa: F401
        have = True
    except ImportError:
        have = False
    if not have:
        with pytest.raises(RuntimeError, match="zstd"):
            pullstorm.run_storm(clients=8, epochs=2, groups=3,
                                per_group=5, churn=1, workers=1,
                                threads=2, force_zstd=True)
        return
    report = pullstorm.run_storm(clients=24, epochs=3, groups=4,
                                 per_group=6, churn=1, workers=1,
                                 threads=4, force_zstd=True)
    assert report["zstd_available"]
    assert report["worker_parity"] == 1
