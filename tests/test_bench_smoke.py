"""CI gate for bench.py's CPU smoke path (CT_BENCH_SMOKE=1).

Locks the overlapped-ingest pipeline into tier-1: run_smoke() asserts
serial/overlap parity (table_count, host_lane, drained counts), the
rediscache serial sets, AND the overlap inequality — overlapped wall
< 0.85 × (decode + device_wait + drain) on the same run — so the
pipeline cannot silently regress to serialized stages without failing
the suite.
"""

import os
import sys

import pytest

from tests.conftest import compile_cache_env

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _share_compile_cache(monkeypatch, tmp_path_factory):
    """One persistent compile cache for a leg's worker subprocesses
    (tools/fleet.py children inherit the environment)."""
    cache = tmp_path_factory.getbasetemp().parent / "fleet-xla-cache"
    for name, value in compile_cache_env(cache).items():
        monkeypatch.setenv(name, value)


@pytest.mark.timeout(120)
def test_bench_smoke_overlap_gate(monkeypatch):
    # Keep the smoke on CPU even outside pytest/conftest (run_smoke
    # also forces the cpu platform itself).
    import jax

    if os.environ.get("CT_TPU_TESTS", "") == "":
        jax.config.update("jax_platforms", "cpu")
    import bench

    out = bench.run_smoke()  # raises BenchError on any parity/gate miss
    assert out["metric"] == "ct_e2e_smoke"
    assert out["smoke_entries"] == out["smoke_table_count"]
    assert out["smoke_overlap_ratio"] < 0.85
    assert out["value"] > 0
    # The stage budget really was measured (not zeroed by a silent
    # metrics-sink regression). Since PR 4 it is SPAN-derived: the
    # smoke traces itself and sums the ingest.decode/submit/drain
    # spans, so a tracer regression zeroes these and fails here.
    assert out["smoke_decode_s"] > 0 and out["smoke_device_wait_s"] > 0
    # The trace artifact exists and tools/traceview.py parses it into
    # per-stage occupancy that shows decode/device/drain overlapping
    # (stage occupancies summing past the overlap ratio's complement
    # is what the 0.85 gate measures; here we pin the artifact path).
    from tools import traceview

    events = traceview.load(out["smoke_trace_path"])
    summary = traceview.stage_summary(
        events, stages=("ingest.decode", "ingest.submit", "ingest.drain"))
    wall = summary.pop("_wall_s")
    assert set(summary) == {"ingest.decode", "ingest.submit",
                            "ingest.drain"}
    assert all(s["busy_s"] > 0 for s in summary.values())
    assert wall > 0
    # Serve leg (ISSUE 5): run_smoke itself gates parity-under-ingest,
    # the span-derived p99 wait budget, and the shed behavior; here we
    # pin that the leg RAN and its numbers are sane — dynamic batching
    # really formed batches (mean lanes/batch > 1, some batch merged
    # several requests), occupancy came from serve.batch spans (a
    # tracer regression zeroes the batch count and fails here), and
    # overload shed explicitly.
    assert out["smoke_serve_parity"] == 1
    assert out["smoke_serve_batches"] > 0
    assert out["smoke_serve_mean_batch_lanes"] > 1.0
    assert out["smoke_serve_max_batch_requests"] > 1
    assert out["smoke_serve_lanes_per_s"] > 0
    assert 0 < out["smoke_serve_wait_p50_ms"] <= out["smoke_serve_wait_p99_ms"]
    assert out["smoke_serve_shed"] > 0
    # Serve-device leg (ISSUE 7): run_smoke gates exact parity under
    # concurrent ingest on the replicated device tier; here we pin the
    # structural numbers — the jitted device contains really executed
    # (span-counted), >=2 replicas answered batches round-robin, the
    # hot-serial cache served hits on the zipf-ish mix, and misses
    # still coalesced into multi-lane batches.
    assert out["smoke_serve_dev_parity"] == 1
    assert out["smoke_serve_dev_replicas"] >= 2
    assert out["smoke_serve_dev_lookups"] > 0
    assert out["smoke_serve_dev_contains_spans"] > 0
    assert out["smoke_serve_dev_cache_hits"] > 0
    assert 0 < out["smoke_serve_dev_cache_hit_rate"] <= 1
    assert out["smoke_serve_dev_mean_batch_lanes"] > 1.0
    assert out["smoke_serve_dev_fallbacks"] == 0
    # Pre-parsed leg: run_smoke itself asserts exact parity with the
    # walker lanes AND that D2H flag traffic stays O(flagged); here we
    # only pin that the leg ran when the native extractor exists (its
    # absence would silently drop the gate).
    from ct_mapreduce_tpu.native import available

    if available():
        # Staged leg (round 11): run_smoke itself gates exact parity
        # with the serial lane, the mean chunks/dispatch hitting K,
        # the ingest.h2d span/bytes instrumentation, H2D hidden behind
        # the envelope's compute, the span-counted execution-fusion
        # structure, and the per-dispatch-toll-modeled >=1.3x
        # acceptance inequality (raw walls are parity-neutral on the
        # 1-core CI box — see the honesty note in run_smoke); here we
        # pin those numbers.
        assert out["smoke_staged_modeled_vs_overlap"] >= 1.3
        assert (out["smoke_staged_execs"]
                * out["smoke_staged_chunks_per_dispatch"]
                <= out["smoke_overlap_execs"])
        assert out["smoke_staged_wall_s"] <= 1.15 * out["smoke_overlap_wall_s"]
        assert out["smoke_staged_chunks_per_dispatch"] > 1
        assert out["smoke_staged_h2d_bytes"] > 0
        assert 0 < out["smoke_staged_h2d_s"] < 0.1 * out["smoke_staged_wall_s"]
        assert out["smoke_preparsed_flag_bytes"] > 0
        # Far below one int32 status row per chunk (the old readback).
        assert out["smoke_preparsed_flag_bytes"] < 4 * out["smoke_entries"]
        # The sharded-preparsed leg ran (host-routed mesh path) with
        # the same O(flagged) compact-readback budget, and the
        # intra-chunk decode-thread parity leg passed.
        assert out["smoke_sharded_preparsed_flag_bytes"] > 0
        assert (out["smoke_sharded_preparsed_flag_bytes"]
                < 4 * out["smoke_entries"])
        assert out["smoke_decode_threads_parity"] == 1


@pytest.mark.timeout(340)
def test_bench_smoke_fleet_gate(tmp_path_factory, monkeypatch):
    """Fleet leg (ISSUE 9): run_fleet_smoke itself gates merged-vs-
    serial parity for W∈{1,2} local worker processes and the
    disjoint+covering partition structure; here we pin that both
    fleets ran with real work and the throughput numbers were
    recorded (honestly — the 1-core box carries no scaling claim;
    parity + structure carry it)."""
    import jax

    if os.environ.get("CT_TPU_TESTS", "") == "":
        jax.config.update("jax_platforms", "cpu")
    # Shared persistent compile cache for the worker subprocesses —
    # all compile identical tiny CPU programs.
    _share_compile_cache(monkeypatch, tmp_path_factory)
    import bench

    out = bench.run_fleet_smoke()  # raises BenchError on any miss
    assert out["metric"] == "ct_fleet_smoke"
    assert out["smoke_fleet_parity"] == 1
    assert out["smoke_fleet_entries"] > 0
    assert out["smoke_fleet_ref_total"] > 0
    assert out["value"] > 0
    assert out["smoke_fleet_w1_entries_per_s"] > 0
    assert out["smoke_fleet_w2_entries_per_s"] > 0
    # The W=1 leg also served the fleet /healthz section live (role,
    # membership, partition map) and observed leader-published
    # checkpoint epochs mid-run.
    assert out["smoke_fleet_healthz_epoch"] >= 1


@pytest.mark.timeout(420)
def test_bench_smoke_obs_gate(tmp_path_factory, monkeypatch):
    """Observability leg (round 23): run_obs_smoke itself gates the
    live W=2 fleet-observability plane — one merged timeline with the
    client's trace_id crossing the process boundary, exact in-body
    AND cross-scrape /metrics/fleet counter parity, the SIGSTOP ->
    /healthz/fleet 503 flip landing within the heartbeat TTL, and the
    modeled tracer+fan-in overhead under 2% of the workers' wall
    (rounds-11/14 convention: raw 1-core walls carry no timing
    claim); here we pin that every sub-leg ran with real work and the
    BENCHLOG numbers were recorded."""
    import jax

    if os.environ.get("CT_TPU_TESTS", "") == "":
        jax.config.update("jax_platforms", "cpu")
    # Shared persistent compile cache for the worker subprocesses —
    # safe here (no SIGKILL/restart sequence; SIGSTOP/SIGCONT and a
    # clean SIGTERM only — see the spawn_worker cache caveat).
    _share_compile_cache(monkeypatch, tmp_path_factory)
    import bench

    out = bench.run_obs_smoke()  # raises BenchError on any miss
    assert out["metric"] == "ct_obs_smoke"
    assert out["value"] > 0
    assert out["smoke_obs_workers"] == 2
    # One timeline, three processes (client + both workers), labeled
    # worker tracks, and at least one request's trace_id observed on
    # both sides of the process boundary.
    assert out["smoke_obs_merged_pids"] >= 3
    assert out["smoke_obs_merged_events"] > 0
    assert 1 <= out["smoke_obs_correlated"] <= out["smoke_obs_trace_ids"]
    # Fan-in parity: exact within the body and across live scrapes.
    assert out["smoke_obs_parity"] == 1
    assert out["smoke_obs_cross_scrape_parity"] == 1
    assert out["smoke_obs_parity_counters"] > 0
    assert out["smoke_obs_insert_total"] > 0
    # The SIGSTOP'd worker degraded the rollup within the TTL and the
    # fleet recovered after SIGCONT.
    assert 0 < out["smoke_obs_flip_s"] <= out["smoke_obs_liveness_s"] + 1.5
    assert out["smoke_obs_recover_s"] > out["smoke_obs_flip_s"]
    # Overhead: modeled from measured per-event costs, gated < 2%.
    assert out["smoke_obs_spans"] > 0
    assert out["smoke_obs_publishes"] > 0
    assert 0 < out["smoke_obs_overhead_pct"] < 2.0


@pytest.mark.timeout(180)
def test_bench_smoke_filter_gate():
    """Filter leg (ISSUE 10): run_filter_smoke itself gates zero false
    negatives over the full included set of a fuzz-populated table,
    capture == drained report, measured FP ≤ 2× target on a disjoint
    probe corpus, and build determinism; here we pin that the leg ran
    with real work and the BENCHLOG numbers were recorded."""
    import jax

    if os.environ.get("CT_TPU_TESTS", "") == "":
        jax.config.update("jax_platforms", "cpu")
    import bench

    out = bench.run_filter_smoke()  # raises BenchError on any miss
    assert out["metric"] == "ct_filter_smoke"
    assert out["value"] > 0
    assert out["smoke_filter_serials"] > 1000
    assert out["smoke_filter_groups"] >= 3
    assert out["smoke_filter_false_negatives"] == 0
    assert out["smoke_filter_fp_measured"] <= 2 * out["smoke_filter_fp_target"]
    assert out["smoke_filter_probes"] >= 10_000
    # Compactness: a cascade, not a serial dump — well under the 128
    # bits a raw fingerprint list would need per entry.
    assert 0 < out["smoke_filter_bits_per_entry"] < 64
    assert out["smoke_filter_max_layers"] >= 1
    # (Filter-over-a-grown-table is pinned by tests/test_filter.py's
    # rehash-mid-corpus fuzz; the smoke stays at the overlap leg's
    # compiled table shape to keep the tier-1 budget.)


@pytest.mark.timeout(180)
def test_bench_smoke_filter_scale_gate():
    """Scaled filter build leg (ISSUE 14): run_filter_scale_smoke
    itself gates byte identity across fused/per-group/streamed/NumPy
    build paths, the fused dispatch collapse, and spill-ring capture
    parity; here we pin that the leg ran with real work and the
    BENCHLOG numbers were recorded."""
    import jax

    if os.environ.get("CT_TPU_TESTS", "") == "":
        jax.config.update("jax_platforms", "cpu")
    import bench

    out = bench.run_filter_scale_smoke()  # raises BenchError on a miss
    assert out["metric"] == "ct_filter_scale_smoke"
    assert out["value"] > 0
    assert out["smoke_fscale_serials"] > 30_000
    assert out["smoke_fscale_groups"] >= 12
    assert out["smoke_fscale_byte_identity"] == 1
    # The collapse is the lever: dispatches well under the
    # per-(group, layer) count the round-15 path would issue.
    assert out["smoke_fscale_dispatches"] < out["smoke_fscale_layers"]
    assert out["smoke_fscale_groups_per_dispatch"] > 2.0
    assert out["smoke_fscale_device_dispatches"] > 0
    # The spill ring really spilled and changed nothing (parity is
    # gated inside the leg).
    assert out["smoke_fscale_spilled_bytes"] > 0
    assert out["smoke_fscale_spill_segments"] >= 1


@pytest.mark.timeout(180)
def test_bench_smoke_distrib_gate():
    """Distribution leg (ISSUE 13): run_distrib_smoke itself gates
    worker byte-identity (full + containers over HTTP), client-side
    delta-chain replay to the exact full filter, and delta+304 traffic
    ≪ full-pull bytes; here we pin that the leg ran every pull class
    with real work and the BENCHLOG numbers were recorded."""
    import jax

    if os.environ.get("CT_TPU_TESTS", "") == "":
        jax.config.update("jax_platforms", "cpu")
    import bench

    out = bench.run_distrib_smoke()  # raises BenchError on any miss
    assert out["metric"] == "ct_distrib_smoke"
    assert out["value"] > 0
    assert out["smoke_distrib_parity"] == 1
    assert out["smoke_distrib_workers"] == 2
    assert out["smoke_distrib_clients"] >= 500
    assert out["smoke_distrib_ratio_304"] > 0.1
    assert out["smoke_distrib_delta_304_vs_full"] < 0.20
    assert out["smoke_distrib_wire_vs_counterfactual"] < 0.5
    assert out["smoke_distrib_pulls"]["304"] > 0
    assert out["smoke_distrib_pulls"]["delta"] > 0
    assert out["smoke_distrib_pulls"]["full"] > 0
    assert 0 < out["smoke_distrib_p50_ms"] <= out["smoke_distrib_p99_ms"]


@pytest.mark.timeout(180)
def test_bench_smoke_ckpt_gate():
    """Incremental checkpoint leg (ISSUE 18): run_ckpt_smoke itself
    gates a 1%-churn CTMRCK02 delta tick >= 5x faster than a full ck01
    save, digest parity between the chain restore, the writer, and the
    ck01 oracle restore, and a bounded chain (anchor observed at
    ckptMaxChain); here we pin that the leg ran with real work and the
    BENCHLOG numbers were recorded."""
    import jax

    if os.environ.get("CT_TPU_TESTS", "") == "":
        jax.config.update("jax_platforms", "cpu")
    import bench

    # 50K entries is the smallest scale the gate accepts; the tier-1
    # wall rides the capped-run dot budget, so don't pay for more here
    # (the 10^7 headline lives in stagecost/BENCHLOG).
    os.environ.setdefault("CT_BENCH_SMOKE_CKPT_ENTRIES", "50000")
    out = bench.run_ckpt_smoke()  # raises BenchError on any miss
    assert out["metric"] == "ct_ckpt_smoke"
    assert out["value"] >= 5.0
    assert out["smoke_ckpt_entries"] >= 50_000
    assert out["smoke_ckpt_tick_ms"] < out["smoke_ckpt_full_ms"]
    assert out["smoke_ckpt_parity"] == 1
    assert out["smoke_ckpt_chain_bounded"] == 1


@pytest.mark.timeout(240)
def test_bench_smoke_verify_gate():
    """Verify leg (ISSUE 8): run_verify_smoke itself gates verdict
    parity vs the host-recomputed truth, the span-counted device
    verify executions, and fallback == undecidable-lane count; here
    we pin that the leg ran with real work on every lane class."""
    import jax

    if os.environ.get("CT_TPU_TESTS", "") == "":
        jax.config.update("jax_platforms", "cpu")
    import bench

    out = bench.run_verify_smoke()  # raises BenchError on any miss
    assert out["metric"] == "ct_verify_smoke"
    assert out["value"] > 0
    assert out["smoke_verify_device_lanes"] > 0
    assert out["smoke_verify_fallback_lanes"] > 0
    assert out["smoke_verify_no_sct"] > 0
    assert out["smoke_verify_no_key"] > 0
    assert out["smoke_verify_verified"] > 0
    assert out["smoke_verify_failed"] > 0
    assert out["smoke_verify_device_execs"] > 0
    assert out["smoke_verify_mean_batch_lanes"] > 1.0
    assert (out["smoke_verify_verified"] + out["smoke_verify_failed"]
            == out["smoke_verify_device_lanes"]
            + out["smoke_verify_fallback_lanes"])
    # Round 17: the windowed precompute engaged — qtable hits beyond
    # the one build per device log key, under the staged queue.
    assert out["smoke_verify_window"] > 0
    assert out["smoke_verify_qtable_misses"] == 2
    assert out["smoke_verify_qtable_hits"] > 0


@pytest.mark.timeout(240)
def test_bench_smoke_audit_gate():
    """Audit leg (round 24): run_audit_smoke itself gates every
    recorded-shard tally against the fixture's ground truth × tile,
    the per-issuer folds against a host-recomputed reference-verifier
    oracle, and quarantined == 0 PINNED on the real corpus; here we
    pin that the leg ran at tier-1 scale (>= 10^5 entries through the
    full decode+verify+aggregate path) with real work in every lane
    class and that divergence was actually measured."""
    import jax

    if os.environ.get("CT_TPU_TESTS", "") == "":
        jax.config.update("jax_platforms", "cpu")
    import bench

    out = bench.run_audit_smoke()  # raises BenchError on any miss
    assert out["metric"] == "ct_audit_smoke"
    assert out["value"] > 0
    assert out["smoke_audit_entries"] >= 100_000
    assert out["smoke_audit_quarantined"] == 0
    assert out["smoke_audit_verified"] > 0
    assert out["smoke_audit_failed"] > 0
    assert out["smoke_audit_no_key"] > 0
    assert out["smoke_audit_retired"] > 0
    assert out["smoke_audit_out_of_interval"] > 0
    assert out["smoke_audit_device_lanes"] > 0
    assert out["smoke_audit_host_lanes"] > 0
    assert out["smoke_audit_per_issuer_groups"] == 8
    # The quarantine pin is only meaningful when the native scanner
    # actually ran against the mirror.
    from ct_mapreduce_tpu.native import load as load_native

    if (os.environ.get("CTMR_NATIVE", "1") != "0"
            and getattr(load_native(), "has_sct", False)):
        assert out["smoke_audit_divergence_measured"] == 1


@pytest.mark.timeout(300)
def test_bench_smoke_tune_gate(monkeypatch):
    """Autotune leg (round 21): run_tune_smoke itself gates a REAL
    scaled-down sweep (staging replay, open-loop serving, ECDSA
    lanes) through the coordinate-descent driver, profile emission
    (fingerprint + provenance), and the end-to-end load check —
    resolve_staging / resolve_serve / resolve_verify returning the
    tuned values through the profile layer alone. Here we pin that
    every sweep really evaluated points and the emitted knobs sit on
    the registry's declared surface. The winning points carry no
    performance claim on this 1-core box (rounds-11/14 convention);
    the real curves come from tools/campaign.py on a device host."""
    import jax

    if os.environ.get("CT_TPU_TESTS", "") == "":
        jax.config.update("jax_platforms", "cpu")
    import bench

    from ct_mapreduce_tpu.tune.registry import SWEEPABLE

    out = bench.run_tune_smoke()  # raises BenchError on any miss
    assert out["metric"] == "ct_tune_smoke"
    assert out["value"] > 0
    assert out["smoke_tune_loaded"] == 1
    assert os.path.exists(out["smoke_tune_profile_path"])
    knobs = out["smoke_tune_knobs"]
    assert set(knobs) == {"staging", "serve", "verify"}
    for section, tuned in knobs.items():
        assert tuned, f"empty tuned section {section}"
        for name in tuned:
            assert name in SWEEPABLE[section]
    for name, st in out["smoke_tune_sweeps"].items():
        assert st["evals"] >= 2, f"{name}: sweep did not search"
        assert st["best_value"] > 0
