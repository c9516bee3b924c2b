"""CTMRFL02 per-group universes (round 20): decoupled deltas and the
dirty-group incremental build path.

Pins the acceptance contract of ISSUE 16:
- cross-format parity: the same corpus compiled as CTMRFL01 and
  CTMRFL02 answers identically over the observed universe (zero false
  negatives in both; fl01 keeps its cross-group exactness, fl02 trades
  it for decoupled bytes — pinned structurally here);
- dirty tracking stays exact across table growth, a fleet merge, and
  a spill-ring restart: the capture layer's incremental content
  hashes always equal a from-scratch recompute, and a warm
  GroupBuildCache reuses clean groups at the OBJECT level (``is``),
  with bytes identical to a from-scratch build;
- the CTMRDL02 delta plane: chain replay is byte-identical at every
  prefix, untouched groups ship zero bytes, mixed-format endpoints
  are refused, and a format rollover publishes a full-snapshot anchor
  instead of a broken delta;
- rev-2 container magics round-trip the format;
- the filterFormat knob ladder (explicit > env > default).
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ct_mapreduce_tpu.agg import merge  # noqa: E402
from ct_mapreduce_tpu.agg.aggregator import (  # noqa: E402
    HostSnapshotAggregator,
    TpuAggregator,
)
from ct_mapreduce_tpu.distrib import (  # noqa: E402
    ChainManifest,
    DeltaError,
    FilterDistributor,
    apply_chain,
    compute_delta,
    decode_container,
    encode_container,
)
from ct_mapreduce_tpu.distrib import delta as delta_mod  # noqa: E402
from ct_mapreduce_tpu.filter import (  # noqa: E402
    FORMAT_FL01,
    FORMAT_FL02,
    GroupBuildCache,
    SpillCaptureRing,
    build_artifact,
    build_from_aggregator,
    build_from_merged,
    content_token,
    default_format,
    normalize_format,
    resolve_filter,
)
from ct_mapreduce_tpu.filter.cache import serial_hash  # noqa: E402
from ct_mapreduce_tpu.utils import minicert  # noqa: E402

ISSUER_DER = minicert.make_cert(serial=1, issuer_cn="Fmt CA",
                                is_ca=True)
ISSUER_DER_B = minicert.make_cert(serial=2, issuer_cn="Fmt CA B",
                                  is_ca=True)


def corpus(n=60, issuer_cn="Fmt CA", issuer=ISSUER_DER, base=1000):
    return [
        (minicert.make_cert(serial=base + s, issuer_cn=issuer_cn,
                            subject_cn=f"fmt{s}.example"), issuer)
        for s in range(n)
    ]


def group_sets(rng, n_groups=5, per_group=30, salt=1):
    return {
        (f"issuer-{g}", 500_000 + 24 * g): {
            bytes([salt, g, s % 251, 9]) + bytes(
                [int(x) for x in rng.integers(0, 256, 2)])
            for s in range(per_group)
        }
        for g in range(n_groups)
    }


def tokens_of(sets):
    return {key: content_token(serials) for key, serials in sets.items()}


# -- cross-format parity --------------------------------------------------


def test_cross_format_parity_over_observed_universe():
    """The same corpus in both formats: every observed (group, serial)
    pair answers True in both — the membership contract is
    format-independent. Structure differs exactly as specified: fl02
    groups hash under ordinal 0 and collapse to a single Bloom layer
    (empty excluded universe); fl01 keeps sorted-issuer ordinals and
    the global excluded universe."""
    sets = group_sets(np.random.default_rng(2026), n_groups=5)
    art01 = build_artifact(sets, fp_rate=0.01, use_device=False,
                           fmt="fl01")
    art02 = build_artifact(sets, fp_rate=0.01, use_device=False,
                           fmt="fl02")
    assert art01.fmt == FORMAT_FL01 and art02.fmt == FORMAT_FL02
    assert art01.to_bytes()[:8] == b"CTMRFL01"
    assert art02.to_bytes()[:8] == b"CTMRFL02"
    for (iss, eh), serials in sorted(sets.items()):
        probe = sorted(serials)
        g01 = art01.group_for(iss, eh)
        g02 = art02.group_for(iss, eh)
        assert art01.query_group(g01, probe).all()
        assert art02.query_group(g02, probe).all()
    ordinals01 = sorted(g.ordinal for g in art01.groups.values())
    assert ordinals01 == list(range(len(sets)))  # sorted-issuer table
    for g in art02.groups.values():
        assert g.ordinal == 0  # no cross-group issuer numbering
        assert len(g.cascade.layers) == 1  # empty excluded set
    # Round-trip preserves the format (and the answers).
    from ct_mapreduce_tpu.filter import FilterArtifact

    back = FilterArtifact.from_bytes(art02.to_bytes())
    assert back.fmt == FORMAT_FL02
    assert back.to_bytes() == art02.to_bytes()


def test_fl02_group_bytes_decoupled_across_corpus_churn():
    """The property the delta plane is built on: adding serials to one
    group AND a whole new first-sorting issuer leaves every other fl02
    group's serialized block byte-identical. Under fl01 the new issuer
    renumbers the sorted ordinal table, re-keying (and so re-building)
    every group."""
    rng = np.random.default_rng(7)
    sets = group_sets(rng, n_groups=4)
    churn_key = sorted(sets)[0]
    sets2 = {k: set(v) for k, v in sets.items()}
    sets2[churn_key] = set(sets2[churn_key]) | {b"\xfe\xed" * 3}
    sets2[("aa-new-issuer", 900_000)] = {b"\x01\x02\x03\x04"}
    for fmt, decoupled in (("fl02", True), ("fl01", False)):
        a1 = build_artifact(sets, fp_rate=0.01, use_device=False,
                            fmt=fmt)
        a2 = build_artifact(sets2, fp_rate=0.01, use_device=False,
                            fmt=fmt)
        moved = sum(
            a1.group_bytes(iss, eh) != a2.group_bytes(iss, eh)
            for (iss, eh) in sorted(sets) if (iss, eh) != churn_key)
        if decoupled:
            assert moved == 0
        else:
            assert moved == len(sets) - 1  # ordinal shift re-keys all


# -- dirty tracking: growth, fleet merge, spill restart -------------------


def test_capture_hashes_exact_across_growth_and_checkpoint(tmp_path):
    """The dict capture's incrementally-maintained per-group hashes
    equal a from-scratch recompute — through table growth (rehash
    mid-corpus) and a checkpoint round-trip."""
    agg = TpuAggregator(capacity=1 << 8, batch_size=64, grow_at=0.5,
                        max_capacity=1 << 14)
    agg.enable_filter_capture()
    agg.ingest(corpus(n=150) + corpus(n=20, issuer_cn="Fmt CA B",
                                      issuer=ISSUER_DER_B,
                                      base=500_000))
    assert agg.capacity > (1 << 8), "growth never fired"
    hashes = agg.capture_content_hashes()
    assert hashes is not None
    for key, serials in sorted(agg.filter_capture.items()):
        assert hashes[key] == content_token(serials)[1]

    path = str(tmp_path / "agg.npz")
    agg.save_checkpoint(path)
    assert "filter_hashes" in np.load(path, allow_pickle=True)
    snap = HostSnapshotAggregator(capacity=1 << 10)
    snap.load_checkpoint(path)
    assert snap.capture_content_hashes() == hashes

    back = TpuAggregator(capacity=1 << 10, batch_size=64)
    back.load_checkpoint(path)
    assert back.capture_content_hashes() == hashes

    # ... and the restored state keeps maintaining them incrementally.
    back.ingest(corpus(n=5, base=9000))
    h2 = back.capture_content_hashes()
    for key, serials in sorted(back.filter_capture.items()):
        assert h2[key] == content_token(serials)[1]


def test_fleet_merge_and_serial_run_agree_on_tokens():
    """A warm cache primed by the MERGED fleet build satisfies the
    serial run's build wholesale (and vice versa): merged tokens
    recompute from union sets, the serial run's come from incremental
    capture hashes, and the two must be the same value — the
    XOR-combine shortcut across workers would cancel shared serials
    and is deliberately not taken."""
    # Overlapping halves: both workers see the first 20 certs.
    half_a = corpus(n=40)
    half_b = corpus(n=40)[:20] + corpus(n=25, issuer_cn="Fmt CA B",
                                        issuer=ISSUER_DER_B,
                                        base=600_000)
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        paths = []
        for w, ents in enumerate((half_a, half_b)):
            agg = TpuAggregator(capacity=1 << 10, batch_size=64)
            agg.enable_filter_capture()
            agg.ingest(ents)
            p = os.path.join(td, f"agg.w{w}.npz")
            agg.save_checkpoint(p)
            paths.append(p)
        serial = TpuAggregator(capacity=1 << 10, batch_size=64)
        serial.enable_filter_capture()
        serial.ingest(half_a + half_b)

        cache = GroupBuildCache()
        art_m = build_from_merged(merge.load_checkpoints(paths),
                                  fp_rate=0.01, cache=cache)
        assert cache.hits == 0  # cold cache: everything built
        art_s = build_from_aggregator(serial, fp_rate=0.01, cache=cache)
        assert cache.hits == len(art_m.groups)  # full reuse
        assert art_s.to_bytes() == art_m.to_bytes()


def test_spill_ring_hash_exactness_contract(tmp_path):
    """Ring hashes are exact only while every captured serial is still
    in the memory tier: a flush (or pre-existing segments at
    construction — the restart case) permanently drops to None, and
    the build path recomputes tokens from the full sets instead."""
    ring = SpillCaptureRing(str(tmp_path / "r1"), mem_bytes=1 << 20)
    key = (1, 500_000)
    ring.add(key, b"\x01\x02")
    ring.add(key, b"\x03\x04")
    ring.add(key, b"\x01\x02")  # duplicate must not double-XOR
    assert ring.content_hashes() == {
        key: serial_hash(b"\x01\x02") ^ serial_hash(b"\x03\x04")}

    spilly = SpillCaptureRing(str(tmp_path / "r2"), mem_bytes=64)
    for j in range(40):
        spilly.add(key, bytes([j]) * 8)
    assert spilly.spilled_bytes > 0
    assert spilly.content_hashes() is None  # flushed → inexact
    del spilly
    resumed = SpillCaptureRing(str(tmp_path / "r2"), mem_bytes=1 << 20)
    assert resumed.content_hashes() is None  # restart → unknown prior


def test_spilled_capture_still_feeds_the_cache(tmp_path):
    """With a flushed ring the aggregator reports no incremental
    hashes, but build_from_aggregator recomputes tokens from the
    serial sets — the second epoch still reuses every clean group."""
    agg = TpuAggregator(capacity=1 << 10, batch_size=64)
    agg.enable_filter_capture(spill_dir=str(tmp_path / "ring"),
                              spill_mem_bytes=256)
    agg.ingest(corpus(n=60))
    assert isinstance(agg.filter_capture, SpillCaptureRing)
    assert agg.filter_capture.spilled_bytes > 0
    assert agg.capture_content_hashes() is None

    cache = GroupBuildCache()
    art1 = build_from_aggregator(agg, fp_rate=0.01, cache=cache)
    art2 = build_from_aggregator(agg, fp_rate=0.01, cache=cache)
    assert cache.hits == len(art1.groups)
    assert art2.to_bytes() == art1.to_bytes()


# -- clean-group reuse is object-level, bytes pinned ----------------------


def test_clean_groups_reused_verbatim_across_epochs():
    """The incremental epoch tick: every clean group in epoch 2 is the
    SAME FilterGroup object epoch 1 built (``is`` — zero rebuild
    work), the churned group rebuilds, and the incremental artifact's
    bytes are identical to a from-scratch fl02 build of epoch 2."""
    rng = np.random.default_rng(41)
    sets1 = group_sets(rng, n_groups=5)
    churn_key = sorted(sets1)[2]
    sets2 = {k: set(v) for k, v in sets1.items()}
    sets2[churn_key] = set(sets2[churn_key]) | {b"\xaa\xbb\xcc\xdd"}

    cache = GroupBuildCache()
    art1 = build_artifact(sets1, fp_rate=0.01, use_device=False,
                          fmt="fl02", cache=cache,
                          tokens=tokens_of(sets1))
    art2 = build_artifact(sets2, fp_rate=0.01, use_device=False,
                          fmt="fl02", cache=cache,
                          tokens=tokens_of(sets2))
    assert cache.hits == len(sets1) - 1
    for key in sorted(sets1):
        iss, eh = key
        same = art2.group_for(iss, eh) is art1.group_for(iss, eh)
        assert same == (key != churn_key)
    scratch = build_artifact(sets2, fp_rate=0.01, use_device=False,
                             fmt="fl02")
    assert art2.to_bytes() == scratch.to_bytes()


def test_cache_ignores_fl01_and_fp_rate_changes():
    """The cache arms only the fl02 path, and a changed target FP rate
    is a miss — a tuned rate must never resurrect stale blocks."""
    sets = group_sets(np.random.default_rng(3), n_groups=3)
    cache = GroupBuildCache()
    build_artifact(sets, fp_rate=0.01, use_device=False, fmt="fl01",
                   cache=cache, tokens=tokens_of(sets))
    assert cache.misses == 0  # fl01 never consulted the cache
    build_artifact(sets, fp_rate=0.01, use_device=False, fmt="fl02",
                   cache=cache, tokens=tokens_of(sets))
    assert cache.hits == 0
    build_artifact(sets, fp_rate=0.02, use_device=False, fmt="fl02",
                   cache=cache, tokens=tokens_of(sets))
    assert cache.hits == 0  # rate change: all dirty


# -- the CTMRDL02 delta plane ---------------------------------------------


def build02(sets):
    return build_artifact(sets, fp_rate=0.01, use_device=False,
                          fmt="fl02").to_bytes()


def test_dl02_chain_replays_every_prefix():
    rng = np.random.default_rng(20260807)
    sets = group_sets(rng, n_groups=6, per_group=25, salt=2)
    blobs = [build02(sets)]
    for step in range(4):
        for key in sorted(sets)[:2]:
            sets[key] = set(sets[key]) | {
                bytes([int(x) for x in rng.integers(0, 256, 5)])
                for _ in range(int(rng.integers(1, 6)))}
        if step == 1:
            sets[("new-issuer", 700_000)] = {b"\x05\x06\x07"}
        if step == 2:
            del sets[sorted(sets)[-1]]
        blobs.append(build02(sets))
    links = [compute_delta(blobs[i], blobs[i + 1], i, i + 1)
             for i in range(len(blobs) - 1)]
    for link in links:
        assert link[:8] == b"CTMRDL02"
        assert delta_mod.delta_format(link) == FORMAT_FL02
    for i in range(1, len(blobs)):
        assert apply_chain(blobs[0], links[:i]) == blobs[i]


def test_dl02_untouched_groups_ship_zero_bytes():
    """Single-group churn: the delta names ONLY the churned group —
    no sparse-XOR salvage, no cross-group patch records at all."""
    rng = np.random.default_rng(11)
    sets = group_sets(rng, n_groups=6)
    churn_key = sorted(sets)[1]
    sets2 = {k: set(v) for k, v in sets.items()}
    sets2[churn_key] = set(sets2[churn_key]) | {b"\x10\x20\x30"}
    b1, b2 = build02(sets), build02(sets2)
    link = compute_delta(b1, b2, 0, 1)
    header, _ = delta_mod.parse_delta(link)
    touched = ([(e["issuer"], e["expHour"]) for e in header["added"]]
               + [(e["issuer"], e["expHour"])
                  for e in header["patched"]])
    assert touched == [churn_key]
    assert header["removed"] == []
    # The wire cost is one group's block plus the JSON header; at
    # fixture scale the header dominates, so only pin that the link
    # undercuts the full artifact — the ≤3% ratio is measured at 10⁷
    # by tools/filtercost.py --delta (round 20).
    assert header["payloadBytes"] < len(b2) / 3
    assert len(link) < len(b2)


def test_mixed_format_delta_refused_and_rollover_anchors():
    sets = group_sets(np.random.default_rng(5), n_groups=3)
    b01 = build_artifact(sets, fp_rate=0.01, use_device=False,
                         fmt="fl01").to_bytes()
    b02 = build02(sets)
    with pytest.raises(DeltaError):
        compute_delta(b01, b02, 0, 1)
    with pytest.raises(DeltaError):
        compute_delta(b02, b01, 0, 1)

    # A format rollover mid-stream publishes a full-snapshot anchor
    # (no delta spans the boundary); the chain resumes in rev 2.
    dist = FilterDistributor()
    assert dist.publish(1, b01)
    assert dist.publish(2, b02)
    man = dist.manifest()
    assert man["format"] == "CTMRDL02"
    assert 2 in man["anchors"]
    assert dist.delta_bundle(1, 2) is None  # anchor in the span
    sets[sorted(sets)[0]].add(b"\x77\x88")
    b3 = build02(sets)
    assert dist.publish(3, b3)
    bundle = dist.delta_bundle(2, 3)
    assert bundle is not None
    ChainManifest.from_json(dist.manifest()).validate_chain(
        2, 3, [bundle])
    assert apply_chain(b02, [bundle]) == b3


# -- rev-2 containers -----------------------------------------------------


def test_container_rev2_magics_round_trip_format():
    sets = group_sets(np.random.default_rng(9), n_groups=3)
    for fmt, mb_magic, cc_magic in (
            ("fl01", b"CTMRMB01", b"CTMRCC01"),
            ("fl02", b"CTMRMB02", b"CTMRCC02")):
        art = build_artifact(sets, fp_rate=0.01, use_device=False,
                             fmt=fmt)
        for kind, magic in (("mlbf", mb_magic), ("clubcard", cc_magic)):
            blob = encode_container(art, kind)
            assert blob[:8] == magic
            back = decode_container(blob)
            assert back.fmt == fmt
            assert back.to_bytes() == art.to_bytes()


# -- the filterFormat knob ladder -----------------------------------------


def test_format_knob_ladder(monkeypatch):
    monkeypatch.delenv("CTMR_FILTER_FORMAT", raising=False)
    assert default_format() == FORMAT_FL02
    assert resolve_filter().fmt == FORMAT_FL02
    monkeypatch.setenv("CTMR_FILTER_FORMAT", "CTMRFL01")
    assert default_format() == FORMAT_FL01
    assert resolve_filter().fmt == FORMAT_FL01
    # Explicit (config directive) outranks env.
    assert resolve_filter(fmt="fl02").fmt == FORMAT_FL02
    # Junk env is ignored by the ladder (config-layer tolerance) ...
    monkeypatch.setenv("CTMR_FILTER_FORMAT", "fl99")
    assert default_format() == FORMAT_FL02
    assert resolve_filter().fmt == FORMAT_FL02
    # ... but a junk explicit value fails loudly.
    with pytest.raises(ValueError):
        resolve_filter(fmt="fl99")
    with pytest.raises(ValueError):
        normalize_format("CTMRFL99")


def test_serve_refresh_reuses_clean_groups():
    """The serve plane's periodic refresh rides the oracle-lifetime
    cache: an unchanged capture rebuilds nothing, and /healthz
    reports the format and the reuse count."""
    from ct_mapreduce_tpu.serve.server import MembershipOracle

    agg = TpuAggregator(capacity=1 << 10, batch_size=64)
    agg.enable_filter_capture()
    agg.ingest(corpus(n=30))
    oracle = MembershipOracle(agg, filter_first=True,
                              max_delay_s=0.001)
    try:
        n_groups = len(oracle.filter_tier.artifact.groups)
        oracle.refresh_filter()
        stats = oracle.stats()
        assert stats["filter_format"] == FORMAT_FL02
        assert stats["filter_groups_reused"] >= n_groups
    finally:
        oracle.close()
