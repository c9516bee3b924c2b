"""Config layering tests (reference: config/config_test.go:8-86 and
config.go:183-214 precedence: defaults < ini < env < CLI flags)."""

import pytest

from ct_mapreduce_tpu.config import CTConfig


def test_defaults(tmp_path):
    cfg = CTConfig.load(argv=[], env={}, default_ini=str(tmp_path / "missing.ini"))
    assert cfg.num_threads == 1
    assert cfg.save_period == "15m"
    assert cfg.polling_delay_mean == "10m"
    assert cfg.polling_delay_std_dev == 10
    assert cfg.output_refresh_period == "125ms"
    assert cfg.health_addr == ":8080"
    assert cfg.redis_timeout == "5s"
    assert not cfg.run_forever and not cfg.log_expired_entries


def test_ini_file_overrides_defaults(tmp_path):
    ini = tmp_path / "ct.ini"
    ini.write_text(
        "numThreads = 7\nlogList = https://a.example/log, https://b.example/log\n"
        "runForever = true\nissuerCNFilter = Let's Encrypt\n"
    )
    cfg = CTConfig.load(argv=["--config", str(ini)], env={})
    assert cfg.num_threads == 7
    assert cfg.run_forever is True
    assert cfg.log_urls() == ["https://a.example/log", "https://b.example/log"]
    assert cfg.issuer_cn_filters() == ["Let's Encrypt"]


def test_env_beats_ini(tmp_path):
    ini = tmp_path / "ct.ini"
    ini.write_text("numThreads = 7\ncertPath = /from/ini\n")
    cfg = CTConfig.load(
        argv=["--config", str(ini)],
        env={"numThreads": "3", "certPath": "/from/env"},
    )
    assert cfg.num_threads == 3
    assert cfg.cert_path == "/from/env"


def test_cli_flags_beat_everything(tmp_path):
    ini = tmp_path / "ct.ini"
    ini.write_text("offset = 5\nlimit = 10\n")
    cfg = CTConfig.load(
        argv=["--config", str(ini), "--offset", "100", "--limit", "200"],
        env={"offset": "50"},
    )
    assert cfg.offset == 100
    assert cfg.limit == 200


def test_unparseable_values_keep_defaults(tmp_path):
    ini = tmp_path / "ct.ini"
    ini.write_text("numThreads = banana\n")
    cfg = CTConfig.load(argv=["--config", str(ini)], env={})
    assert cfg.num_threads == 1


def test_tpu_directives(tmp_path):
    ini = tmp_path / "ct.ini"
    ini.write_text("backend = tpu\nbatchSize = 131072\ntableBits = 24\n"
                   "tableGrowAt = 0.8\ntableMaxBits = 26\n")
    cfg = CTConfig.load(argv=["--config", str(ini)], env={})
    assert cfg.backend == "tpu"
    assert cfg.batch_size == 131072
    assert cfg.table_bits == 24
    assert cfg.table_grow_at == 0.8
    assert cfg.table_max_bits == 26
    cfg2 = CTConfig.load(argv=["--config", str(ini), "--backend", "redis"], env={})
    assert cfg2.backend == "redis"
    # Env beats file; unparseable env falls back (config.go:41-123 quirk).
    cfg3 = CTConfig.load(argv=["--config", str(ini)],
                         env={"tableGrowAt": "0.5"})
    assert cfg3.table_grow_at == 0.5
    cfg4 = CTConfig.load(argv=["--config", str(ini)],
                         env={"tableGrowAt": "banana"})
    assert cfg4.table_grow_at == 0.8
    # Growth disabled and ceilings flow into the aggregator factory.
    from ct_mapreduce_tpu.models.ingest_model import build_aggregator

    ini2 = tmp_path / "ct2.ini"
    ini2.write_text("backend = tpu\ntableBits = 10\nmeshShape = shard:1\n"
                    "tableGrowAt = 0\ntableMaxBits = 20\nbatchSize = 64\n")
    agg = build_aggregator(CTConfig.load(argv=["--config", str(ini2)], env={}))
    assert agg.grow_at == 0
    # The configured 2^20 ceiling is floored to the largest capacity
    # the active layout can actually build (bucket: 24·2^k), so the
    # at-ceiling growth guard can fire (ADVICE r05 grow-livelock fix).
    assert agg.max_capacity == agg._layout_capacity_floor(1 << 20)
    assert 0 < agg.max_capacity <= 1 << 20


def test_usage_mentions_every_reference_directive():
    text = CTConfig().usage()
    for directive in (
        "certPath",
        "redisHost",
        "issuerCNFilter",
        "runForever",
        "pollingDelayMean",
        "pollingDelayStdDev",
        "logExpiredEntries",
        "numThreads",
        "savePeriod",
        "logList",
        "outputRefreshPeriod",
        "statsRefreshPeriod",
        "statsdHost",
        "statsdPort",
        "redisTimeout",
        "healthAddr",
    ):
        assert directive in text, f"usage() missing {directive}"


def test_observability_directives(tmp_path):
    """tracePath / metricsPort (PR 4): ini + env layering, ints parse,
    and usage() documents both."""
    ini = tmp_path / "ct.ini"
    ini.write_text("tracePath = /tmp/run-trace.json\nmetricsPort = 9464\n")
    cfg = CTConfig.load(argv=["--config", str(ini)], env={})
    assert cfg.trace_path == "/tmp/run-trace.json"
    assert cfg.metrics_port == 9464
    # Env beats file; unparseable env falls back to the file value.
    cfg2 = CTConfig.load(argv=["--config", str(ini)],
                         env={"metricsPort": "9000"})
    assert cfg2.metrics_port == 9000
    cfg3 = CTConfig.load(argv=["--config", str(ini)],
                         env={"metricsPort": "banana"})
    assert cfg3.metrics_port == 9464
    # Defaults: both off.
    off = CTConfig.load(argv=[], env={})
    assert off.trace_path == "" and off.metrics_port == 0
    usage = CTConfig().usage()
    assert "tracePath" in usage and "metricsPort" in usage


REMOVED_DISPATCH_DIRECTIVES = {"overlapWorkers": "overlap_workers",
                               "chunksPerDispatch": "chunks_per_dispatch",
                               "stagingDepth": "staging_depth"}


@pytest.mark.parametrize("where", ["ini", "environment"])
@pytest.mark.parametrize("directive", sorted(REMOVED_DISPATCH_DIRECTIVES))
def test_removed_dispatch_directives_are_ignored(tmp_path, directive, where):
    """PR 46 removed the overlap scheduler and the staged ring with
    their directives. A deployment's old ini or environment still names
    them: it loads as before (``CTConfig.load`` ignores names it does
    not know, like config.go), nothing of the name is left on the
    config or in the usage text, and ``ct-fetch`` builds its sink, the
    one dispatch path, from it."""
    from ct_mapreduce_tpu.cmd import ct_fetch
    from ct_mapreduce_tpu.ingest.sync import AggregatorSink

    ini = tmp_path / "old.ini"
    ini.write_text("backend = tpu\nbatchSize = 64\ntableBits = 12\n"
                   "meshShape = shard:1\ndeviceQueueDepth = 3\n"
                   + (f"{directive} = 2\n" if where == "ini" else ""))
    env = {directive: "2"} if where == "environment" else {}
    cfg = CTConfig.load(argv=["--config", str(ini)], env=env)
    assert cfg.backend == "tpu" and cfg.device_queue_depth == 3
    assert not hasattr(cfg, REMOVED_DISPATCH_DIRECTIVES[directive])
    assert directive not in CTConfig().usage()
    sink, model = ct_fetch.build_sink(cfg, database=None)
    assert isinstance(sink, AggregatorSink) and model is not None
    assert sink.device_queue_depth == 3
    assert not hasattr(sink, REMOVED_DISPATCH_DIRECTIVES[directive])
    sink.close()


def test_query_port_directive(tmp_path):
    """queryPort (ISSUE 5): ini + env layering, int parse, usage()."""
    ini = tmp_path / "ct.ini"
    ini.write_text("queryPort = 9090\n")
    cfg = CTConfig.load(argv=["--config", str(ini)], env={})
    assert cfg.query_port == 9090
    cfg2 = CTConfig.load(argv=["--config", str(ini)],
                         env={"queryPort": "9999"})
    assert cfg2.query_port == 9999
    cfg3 = CTConfig.load(argv=["--config", str(ini)],
                         env={"queryPort": "banana"})
    assert cfg3.query_port == 9090
    assert CTConfig.load(argv=[], env={}).query_port == 0  # default off
    assert "queryPort" in CTConfig().usage()


def test_serve_tier_directives(tmp_path):
    """serveReplicas / serveDevice / serveCacheSize (ISSUE 7): ini +
    env layering, bool/int parse, defaults, usage()."""
    ini = tmp_path / "ct.ini"
    ini.write_text(
        "serveReplicas = 4\nserveDevice = false\nserveCacheSize = 512\n")
    cfg = CTConfig.load(argv=["--config", str(ini)], env={})
    assert cfg.serve_replicas == 4
    assert cfg.serve_device is False
    assert cfg.serve_cache_size == 512
    cfg2 = CTConfig.load(
        argv=["--config", str(ini)],
        env={"serveReplicas": "8", "serveDevice": "true",
             "serveCacheSize": "-1"})
    assert cfg2.serve_replicas == 8
    assert cfg2.serve_device is True
    assert cfg2.serve_cache_size == -1
    # Unparseable env falls back to the file value.
    cfg3 = CTConfig.load(argv=["--config", str(ini)],
                         env={"serveReplicas": "many"})
    assert cfg3.serve_replicas == 4
    # Defaults: pool/cache auto-sized downstream (resolve_serve),
    # device serving on.
    dflt = CTConfig.load(argv=[], env={})
    assert dflt.serve_replicas == 0
    assert dflt.serve_device is True
    assert dflt.serve_cache_size == 0
    usage = CTConfig().usage()
    for d in ("serveReplicas", "serveDevice", "serveCacheSize"):
        assert d in usage


def test_verify_directives(tmp_path):
    """verifySignatures / verifyLogKeys (ISSUE 8): ini + env layering,
    bool parse, defaults, usage(). The CTMR_VERIFY env equivalent
    layers downstream (verify.lane.resolve_verify, covered by
    tests/test_verify_lane.py)."""
    ini = tmp_path / "ct.ini"
    ini.write_text(
        "verifySignatures = true\nverifyLogKeys = /etc/ct/keys.json\n")
    cfg = CTConfig.load(argv=["--config", str(ini)], env={})
    assert cfg.verify_signatures is True
    assert cfg.verify_log_keys == "/etc/ct/keys.json"
    cfg2 = CTConfig.load(
        argv=["--config", str(ini)],
        env={"verifySignatures": "false",
             "verifyLogKeys": "/run/keys.json"})
    assert cfg2.verify_signatures is False
    assert cfg2.verify_log_keys == "/run/keys.json"
    # Unparseable env bool falls back to the file value.
    cfg3 = CTConfig.load(argv=["--config", str(ini)],
                         env={"verifySignatures": "maybe"})
    assert cfg3.verify_signatures is True
    dflt = CTConfig.load(argv=[], env={})
    assert dflt.verify_signatures is False
    assert dflt.verify_log_keys == ""
    usage = CTConfig().usage()
    for d in ("verifySignatures", "verifyLogKeys"):
        assert d in usage


def test_verify_precomp_directives(tmp_path):
    """verifyPrecompWindow / verifyQTableSize (ISSUE 12): ini + env
    layering, int parse, sentinel defaults (-1 window = unset, so an
    explicit 0 — the legacy ladder — survives a stray env), usage().
    The CTMR_VERIFY_PRECOMP_WINDOW / CTMR_VERIFY_QTABLE_SIZE env
    equivalents layer downstream (verify.lane.resolve_verify, covered
    by tests/test_verify_lane.py)."""
    ini = tmp_path / "ct.ini"
    ini.write_text("verifyPrecompWindow = 0\nverifyQTableSize = 48\n")
    cfg = CTConfig.load(argv=["--config", str(ini)], env={})
    assert cfg.verify_precomp_window == 0
    assert cfg.verify_qtable_size == 48
    cfg2 = CTConfig.load(
        argv=["--config", str(ini)],
        env={"verifyPrecompWindow": "4", "verifyQTableSize": "junk"})
    assert cfg2.verify_precomp_window == 4
    assert cfg2.verify_qtable_size == 48  # unparseable env ignored
    dflt = CTConfig.load(argv=[], env={})
    assert dflt.verify_precomp_window == -1  # unset sentinel
    assert dflt.verify_qtable_size == 0
    usage = CTConfig().usage()
    for d in ("verifyPrecompWindow", "verifyQTableSize"):
        assert d in usage


def test_fleet_directives(tmp_path, monkeypatch):
    """numWorkers / workerId / checkpointPeriod / coordinatorBackend
    (ISSUE 9): ini + env layering, int parse, defaults, usage() — and
    the CTMR_* env fallback behind the config values
    (fleet.resolve_fleet)."""
    ini = tmp_path / "ct.ini"
    ini.write_text(
        "numWorkers = 4\nworkerId = 2\ncheckpointPeriod = 30s\n"
        "coordinatorBackend = redis\n")
    cfg = CTConfig.load(argv=["--config", str(ini)], env={})
    assert cfg.num_workers == 4
    assert cfg.worker_id == 2
    assert cfg.checkpoint_period == "30s"
    assert cfg.coordinator_backend == "redis"
    # Env beats file; unparseable env int falls back to the file value.
    cfg2 = CTConfig.load(
        argv=["--config", str(ini)],
        env={"numWorkers": "8", "workerId": "5",
             "checkpointPeriod": "1m", "coordinatorBackend": "jax"})
    assert cfg2.num_workers == 8 and cfg2.worker_id == 5
    assert cfg2.checkpoint_period == "1m"
    assert cfg2.coordinator_backend == "jax"
    cfg3 = CTConfig.load(argv=["--config", str(ini)],
                         env={"numWorkers": "banana"})
    assert cfg3.num_workers == 4
    # Defaults: single worker, resolution deferred to resolve_fleet
    # (workerId's unset sentinel is -1 — 0 is a real, pinnable id).
    dflt = CTConfig.load(argv=[], env={})
    assert dflt.num_workers == 0 and dflt.worker_id == -1
    assert dflt.checkpoint_period == "" and dflt.coordinator_backend == ""
    from ct_mapreduce_tpu.ingest.fleet import resolve_fleet

    for k in ("CTMR_NUM_WORKERS", "CTMR_WORKER_ID",
              "CTMR_CHECKPOINT_PERIOD", "CTMR_COORDINATOR"):
        monkeypatch.delenv(k, raising=False)
    assert resolve_fleet(dflt.num_workers, dflt.worker_id,
                         dflt.checkpoint_period,
                         dflt.coordinator_backend) == (1, 0, "", "")
    monkeypatch.setenv("CTMR_NUM_WORKERS", "6")
    monkeypatch.setenv("CTMR_CHECKPOINT_PERIOD", "45s")
    assert resolve_fleet(dflt.num_workers, dflt.worker_id,
                         dflt.checkpoint_period,
                         dflt.coordinator_backend) == (6, 0, "45s", "")
    # An ini that explicitly pins workerId = 0 beats a stray env id.
    monkeypatch.setenv("CTMR_WORKER_ID", "4")
    pinned = CTConfig.load(
        argv=["--config", str(ini)], env={"workerId": "0"})
    assert pinned.worker_id == 0
    assert resolve_fleet(pinned.num_workers, pinned.worker_id,
                         "", "")[1] == 0
    # ...while an UNSET workerId still takes the env value.
    assert resolve_fleet(dflt.num_workers, dflt.worker_id, "", "")[1] == 4
    usage = CTConfig().usage()
    for d in ("numWorkers", "workerId", "checkpointPeriod",
              "coordinatorBackend"):
        assert d in usage


def test_platform_profile_feeds_every_resolver(tmp_path, monkeypatch):
    """platformProfile (ISSUE 13, ROADMAP item 1's unlocking
    refactor): ONE data file supplies tuned knobs to every subsystem's
    resolve_*, with the shared ladder explicit > env > profile >
    default — a tuned device profile needs no code change."""
    import json

    for k in ("CTMR_PLATFORM_PROFILE", "CTMR_SERVE_REPLICAS",
              "CTMR_SERVE_DEVICE", "CTMR_SERVE_CACHE_SIZE",
              "CTMR_VERIFY", "CTMR_VERIFY_BATCH",
              "CTMR_VERIFY_PRECOMP_WINDOW", "CTMR_NUM_WORKERS",
              "CTMR_EMIT_FILTER", "CTMR_FILTER_FP_RATE"):
        monkeypatch.delenv(k, raising=False)
    from ct_mapreduce_tpu.filter import resolve_filter
    from ct_mapreduce_tpu.ingest.fleet import resolve_fleet
    from ct_mapreduce_tpu.serve.server import resolve_serve
    from ct_mapreduce_tpu.verify.lane import resolve_verify

    prof = tmp_path / "tuned.json"
    prof.write_text(json.dumps({
        "version": 1, "platform": "test-box",
        "knobs": {
            "serve": {"serveReplicas": 5, "serveDevice": False,
                      "serveCacheSize": 512},
            "verify": {"verifyBatch": 4096, "verifyPrecompWindow": 4},
            "fleet": {"numWorkers": 4},
            "filter": {"filterFpRate": 0.005},
        }}))
    monkeypatch.setenv("CTMR_PLATFORM_PROFILE", str(prof))
    # Profile supplies the defaults...
    assert resolve_serve() == (5, False, 512)
    assert resolve_verify()[2] == 4096
    assert resolve_verify()[3] == 4
    assert resolve_fleet()[0] == 4
    assert resolve_filter()[2] == 0.005
    # ...env beats profile...
    monkeypatch.setenv("CTMR_SERVE_REPLICAS", "9")
    monkeypatch.setenv("CTMR_VERIFY_PRECOMP_WINDOW", "8")
    assert resolve_serve() == (9, False, 512)
    assert resolve_verify()[3] == 8
    # ...and an explicit directive/kwarg beats both (incl. the
    # 0-is-real sentinel knobs).
    assert resolve_serve(cache_size=64) == (9, False, 64)
    assert resolve_verify(window=0)[3] == 0
    # An unreadable profile resolves as if absent (no crash).
    monkeypatch.setenv("CTMR_PLATFORM_PROFILE", str(tmp_path / "nope"))
    monkeypatch.delenv("CTMR_SERVE_REPLICAS")
    assert resolve_serve() == (2, True, 4096)
    # The directive parses and is documented.
    ini = tmp_path / "p.ini"
    ini.write_text(f"platformProfile = {prof}\ndistribHistory = 6\n"
                   "maxDeltaChain = 3\n")
    cfg = CTConfig.load(argv=["--config", str(ini)], env={})
    assert cfg.platform_profile == str(prof)
    assert cfg.distrib_history == 6 and cfg.max_delta_chain == 3
    usage = CTConfig().usage()
    for d in ("platformProfile", "distribHistory", "maxDeltaChain"):
        assert d in usage


def _profile_file(tmp_path, name: str, doc: dict) -> str:
    import json

    path = str(tmp_path / name)
    with open(path, "w") as fh:
        json.dump(doc, fh)
    return path


def test_fingerprint_match_and_mismatch(tmp_path):
    import os

    from ct_mapreduce_tpu.config import profile as platprofile

    base = {"version": 1, "knobs": {"serve": {"serveReplicas": 3}}}
    ok = _profile_file(tmp_path, "ok.json", dict(
        base, fingerprint={"host_cores": os.cpu_count() or 1}))
    bad = _profile_file(tmp_path, "bad.json", dict(
        base, fingerprint={"host_cores": -1}))
    legacy = _profile_file(tmp_path, "legacy.json", base)  # no fingerprint
    try:
        assert platprofile.load_profile(ok) is not None
        assert platprofile.load_profile(bad) is None  # warn + ignore
        assert platprofile.load_profile(legacy) is not None
        # Partial fingerprints compare only shared keys.
        assert platprofile.fingerprint_matches({})
        assert platprofile.fingerprint_matches(
            {"unknown_key": "whatever"})
        assert not platprofile.fingerprint_matches(
            {"host_cores": -1}, {"host_cores": 4})
    finally:
        platprofile.invalidate_cache()


def test_provenance_tolerant_load(tmp_path):
    from ct_mapreduce_tpu.config import profile as platprofile

    base = {"version": 1, "knobs": {"serve": {"serveReplicas": 2}}}
    odd = _profile_file(tmp_path, "odd.json", dict(
        base, provenance={
            "future_section": {"future_measure": {"anything": [1]}}},
        extra_future_block=42))
    bad = _profile_file(tmp_path, "bad.json", dict(
        base, provenance=["not", "a", "dict"]))
    try:
        loaded = platprofile.load_profile(odd)
        assert loaded is not None  # unknown provenance content is fine
        assert loaded["knobs"]["serve"]["serveReplicas"] == 2
        assert platprofile.load_profile(bad) is None  # wrong shape
    finally:
        platprofile.invalidate_cache()
