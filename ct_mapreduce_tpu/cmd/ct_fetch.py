"""ct-fetch: continuous CT-log ingest.

The reference binary (/root/reference/cmd/ct-fetch/ct-fetch.go:490-638):
config init → storage wiring → telemetry → sync engine + store workers
→ one downloader per log → health endpoint → signal-driven shutdown →
optional runForever polling loop.

This build adds ``backend = tpu``: entries are packed into device
batches and reduced on-chip by :class:`TpuAggregator` instead of
per-entry Redis round-trips; device aggregates snapshot to
``aggStatePath`` for ``storage-statistics --backend=tpu``.
"""

from __future__ import annotations

import contextlib
import os
import signal
import sys
import threading
import time
from ct_mapreduce_tpu.config import CTConfig
from ct_mapreduce_tpu.engine import get_configured_storage, prepare_telemetry
from ct_mapreduce_tpu.ingest.fleet import (
    FleetService,
    build_coordinator,
    resolve_fleet,
    worker_state_path,
)
from ct_mapreduce_tpu.ingest.health import HealthServer
from ct_mapreduce_tpu.ingest.sync import (
    AggregatorSink,
    DatabaseSink,
    LogSyncEngine,
    polling_delay,
)
from ct_mapreduce_tpu.telemetry import flight, trace
from ct_mapreduce_tpu.telemetry.promhttp import MetricsServer
from ct_mapreduce_tpu.utils import parse_duration


class ProgressPrinter:
    """Textual stand-in for the reference's mpb progress bars
    (ct-fetch.go:317-330); disabled by -nobars."""

    def __init__(self, engine: LogSyncEngine, period_s: float):
        self.engine = engine
        self.period_s = max(period_s, 0.05)
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._last: dict[str, tuple[float, int]] = {}

    def _line(self) -> str:
        parts = []
        now = time.monotonic()
        for url, (pos, end) in sorted(self.engine.progress().items()):
            prev_t, prev_pos = self._last.get(url, (now, pos))
            rate = (pos - prev_pos) / (now - prev_t) if now > prev_t else 0.0
            self._last[url] = (now, pos)
            pct = 100.0 * pos / end if end else 100.0
            # ETA decorator parity with the reference's mpb bars
            # (ct-fetch.go:317-330).
            if rate > 0 and end > pos:
                secs = (end - pos) / rate
                eta = (f"{secs / 3600:.1f}h" if secs >= 3600
                       else f"{secs / 60:.0f}m" if secs >= 60
                       else f"{secs:.0f}s")
            else:
                eta = "--"
            parts.append(
                f"{url}: {pos}/{end} ({pct:.1f}%) {rate:,.0f}/s eta {eta}"
            )
        return " | ".join(parts)

    def _run(self) -> None:
        while not self._stop.wait(self.period_s):
            line = self._line()
            if line:
                print(f"\r{line}", end="", file=sys.stderr, flush=True)

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, name="progress",
                                        daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread:
            self._thread.join()
        print(file=sys.stderr)


def announce_device() -> bool:
    """``backend = tpu`` start-up: say which device JAX gave this
    process (platform, kind, count). False when that is JAX's own CPU
    fallback — a run that was meant for the chip must not finish on the
    host with exit code 0. ``JAX_PLATFORMS`` naming ``cpu`` is how a
    CPU run is asked for on purpose."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    print(f"device: platform={platform} kind={devices[0].device_kind} "
          f"count={len(devices)}", file=sys.stderr)
    asked = os.environ.get("JAX_PLATFORMS", "").lower().split(",")
    if platform == "cpu" and "cpu" not in asked:
        print("error: backend = tpu, but JAX found no accelerator and "
              "fell back to the CPU (set JAX_PLATFORMS=cpu to run there "
              "on purpose)", file=sys.stderr)
        return False
    return True


def build_sink(config: CTConfig, database, backend=None):
    """Pick the store path: per-entry host store (reference parity) or
    the batched device pipeline (single-chip or mesh-sharded per
    meshShape — see models.build_aggregator)."""
    if config.backend == "tpu":
        from ct_mapreduce_tpu.models import IngestModel

        model = IngestModel.from_config(config)
        # certPath keeps the reference's durable PEM tree even in TPU
        # mode; without it the backend is a no-op and is skipped.
        pem_backend = backend if config.cert_path else None
        return AggregatorSink(model.aggregator,
                              flush_size=config.batch_size,
                              backend=pem_backend,
                              device_queue_depth=config.device_queue_depth,
                              decode_workers=config.decode_workers,
                              decode_threads=config.decode_threads,
                              preparsed=config.preparsed_ingest or None,
                              verify_signatures=(config.verify_signatures
                                                 or None),
                              verify_log_keys=(config.verify_log_keys
                                               or None),
                              verify_precomp_window=(
                                  config.verify_precomp_window
                                  if config.verify_precomp_window >= 0
                                  else None),
                              verify_qtable_size=config.verify_qtable_size,
                              ), model
    sink = DatabaseSink(
        database,
        cn_filters=tuple(config.issuer_cn_filters()),
        log_expired_entries=config.log_expired_entries,
    )
    return sink, None


def fleet_assignments(fleet, log_urls: list[str],
                      takeover: bool = False,
                      errors: list | None = None) -> list[tuple]:
    """This worker's share of the feed as (url, offset, limit,
    state_suffix) download assignments. Multi-log fleets partition
    whole logs by rendezvous hash, then take the per-log fetch lease
    on each — a log whose lease another worker still holds (takeover
    racing the owner's restart) is skipped this round and re-contended
    next round, so no log is ever fetched by two workers at once. A
    fleet pointed at ONE log stripes its entry-index space instead
    (one STH fetch resolves the tree size), each stripe with its own
    durable cursor key; an STH failure is recorded in ``errors`` and
    yields an empty round (retried on the next poll) instead of
    killing the worker."""
    if fleet is None:
        return [(u, None, None, "") for u in log_urls]
    if fleet.num_workers <= 1:
        # Degenerate fleet: worker 0 owns everything, but the map
        # still computes so /healthz surfaces it.
        return [(u, None, None, "") for u in fleet.partition(log_urls)]
    if len(log_urls) == 1:
        from ct_mapreduce_tpu.ingest.ctclient import CTLogClient

        url = log_urls[0]
        try:
            tree_size = CTLogClient(url).get_sth().tree_size
        except Exception as err:
            if errors is not None:
                errors.append(
                    f"{url}: STH fetch for stripe assignment failed: "
                    f"{err}")
            return []
        offset, limit = fleet.stripe(tree_size)
        fleet.note_stripe(url, offset, limit)
        if limit <= 0:
            return []  # more workers than entries: nothing for us
        return [(url, offset, limit, f"#w{fleet.worker_id}")]
    return [(u, None, None, "")
            for u in fleet.partition(log_urls, takeover=takeover)
            if fleet.claim(u)]


def main(argv: list[str] | None = None) -> int:
    config = CTConfig.load(argv)
    log_urls = config.log_urls()
    if not log_urls:
        print(config.usage(), file=sys.stderr)
        print("\nerror: logList is required", file=sys.stderr)
        return 2

    # Platform profile (round 18): pin the tuned-knob data file before
    # any subsystem resolves its knobs — every resolve_* from here on
    # reads the profile layer (explicit > env > profile > default).
    from ct_mapreduce_tpu.config import profile as platprofile

    platprofile.set_active_profile(config.platform_profile)

    # Fleet resolution before any state path is used: each worker of a
    # multi-worker ingest keeps its own aggregate snapshot
    # (agg.npz → agg.w<id>.npz); storage-statistics merges them
    # (aggStatePath glob) into one view.
    num_workers, fleet_worker_id, checkpoint_period, coord_backend = (
        resolve_fleet(config.num_workers, config.worker_id,
                      config.checkpoint_period, config.coordinator_backend))
    if fleet_worker_id >= num_workers:
        print(f"error: workerId {fleet_worker_id} outside "
              f"[0, numWorkers={num_workers})", file=sys.stderr)
        return 2
    if config.backend == "tpu":
        from ct_mapreduce_tpu.utils import compile_cache

        compile_cache.configure()  # before the first trace
        if not announce_device():
            return 2
    base_state_path = config.agg_state_path
    config.agg_state_path = worker_state_path(
        config.agg_state_path, fleet_worker_id, num_workers)
    # A durable per-worker checkpoint on disk means this process is a
    # WARM RESTART rejoining a fleet that already crossed its start
    # barrier: it must not re-run the barrier (peers may have finished;
    # a stale leader lease would strand it polling a dead started key)
    # and its first round must partition against the LIVE membership so
    # logs a survivor took over aren't double-fetched.
    resuming = bool(config.agg_state_path
                    and os.path.exists(config.agg_state_path))

    database, _cache, _backend = get_configured_storage(config)  # noqa: F841
    dumper = prepare_telemetry("ct-fetch", config)
    # Span tracing: tracePath directive (CTMR_TRACE env auto-enables at
    # import). Near-zero cost when off; exported at shutdown.
    if config.trace_path:
        trace.enable(config.trace_path)
    # Cross-process correlation (round 23): every span this process
    # emits carries its fleet worker id; the leader-epoch attr joins in
    # FleetService._observe_epoch as epochs advance.
    trace.set_process_attrs(worker=fleet_worker_id)
    # Fleet observability knobs: fan-in on/off + the SLO thresholds
    # (directives > CTMR_SLO_* env > platform profile > disabled).
    from ct_mapreduce_tpu.telemetry import fleetobs
    from ct_mapreduce_tpu.telemetry import metrics as _metrics

    obs = fleetobs.resolve_obs(
        fleet_metrics=config.fleet_metrics,
        max_ingest_lag=config.slo_max_ingest_lag,
        max_ckpt_age_s=config.slo_max_checkpoint_age,
        max_filter_lag=config.slo_max_filter_lag,
        max_serve_p99_ms=config.slo_max_serve_p99_ms)
    # Flight recorder: a crash, SIGTERM/SIGUSR1, or wedged-pipeline
    # latch dumps the trace ring + last metric snapshots next to the
    # run (CTMR_FLIGHT_DIR overrides the directory). Signal dumps ride
    # this process's own handlers below; the unhandled-exception dump
    # is the except clause around the main loop (no sys.excepthook
    # mutation — main() must leave no global hooks behind, it is
    # re-entered by tests and runForever wrappers). Uninstalled in the
    # finally for the same reason.
    flight.install(signals=False, excepthook=False)
    # Lock-order witness (round 16): CTMR_LOCK_WITNESS=1 wraps every
    # lock the package creates from here on; order violations and
    # cycles land in this run's flight dumps as a `lock_witness`
    # section (docs/ANALYSIS.md). No-op unless the env opts in.
    from ct_mapreduce_tpu.analysis import witness as _witness

    _witness.install()
    if config.issuer_cn_filter:
        # The reference logs a stale "unsupported" warning here
        # (ct-fetch.go:498-499) but enforces the filter anyway; we just
        # enforce it.
        print(f"IssuerCNFilter enabled: {config.issuer_cn_filters()}",
              file=sys.stderr)

    run_stage = {"stage": "init"}
    sink, model = build_sink(config, database, _backend)

    # Filter emission (round 15): emitFilter compiles the aggregation
    # state's per-(issuer, expDate) serial sets into a crlite-style
    # filter-cascade artifact on every checkpoint save. Fleet workers
    # get per-worker artifact paths (like their snapshots); the leader
    # additionally emits the MERGED fleet filter each epoch below.
    from ct_mapreduce_tpu.filter import resolve_filter

    fknobs = resolve_filter(
        config.emit_filter or None, config.filter_path,
        config.filter_fp_rate, state_path=base_state_path,
        spill_dir=config.filter_capture_spill_dir,
        spill_mb=config.filter_capture_spill_mb,
        stream_chunk=config.filter_stream_chunk,
        fused_lanes=config.filter_fused_lanes,
        fmt=config.filter_format)
    emit_filter, base_filter_path, filter_fp = (
        fknobs.emit, fknobs.path, fknobs.fp_rate)
    if emit_filter and model is not None:
        model.aggregator.configure_filter_emission(
            worker_state_path(base_filter_path, fleet_worker_id,
                              num_workers),
            filter_fp,
            spill_dir=(worker_state_path(fknobs.spill_dir,
                                         fleet_worker_id, num_workers)
                       if fknobs.spill_dir else ""),
            spill_mem_bytes=fknobs.spill_mb << 20,
            fmt=fknobs.fmt)
    elif emit_filter:
        print("emitFilter ignored: filter emission needs backend = tpu",
              file=sys.stderr)
        emit_filter = False

    # Checkpoint plane (round 22): pin the CTMRCK02 knobs from the
    # directives; unset ones resolve through CTMR_* env and the
    # platform profile inside the aggregator.
    if model is not None:
        model.aggregator.configure_checkpointing(
            mode=config.checkpoint_mode,
            max_chain=config.ckpt_max_chain,
            segment_budget_mb=config.ckpt_segment_budget_mb)

    # Leader-side incremental build cache: across epoch ticks only
    # churned groups of the merged fleet filter rebuild (tokens always
    # recompute from the merged union sets — never worker hashes).
    from ct_mapreduce_tpu.filter import GroupBuildCache

    fleet_filter_cache = GroupBuildCache()

    def leader_fleet_filter() -> None:
        """Leader epoch-tick duty: fold every worker snapshot present
        on disk (agg/merge.py) and emit the merged fleet filter —
        best-effort per tick (a worker mid-checkpoint contributes its
        previous snapshot; the next epoch catches it up)."""
        if not emit_filter or num_workers <= 1:
            return
        if fleet is None or not fleet.is_leader:
            return
        from ct_mapreduce_tpu.agg import merge as aggmerge
        from ct_mapreduce_tpu.filter import artifact as fartifact
        from ct_mapreduce_tpu.telemetry.metrics import incr_counter

        paths = [
            p for p in (worker_state_path(base_state_path, w, num_workers)
                        for w in range(num_workers))
            if os.path.exists(p)
        ]
        if not paths:
            return
        try:
            merged = aggmerge.load_checkpoints(paths)
            art = fartifact.build_from_merged(
                merged, fp_rate=filter_fp, allow_partial=True,
                fmt=fknobs.fmt, cache=fleet_filter_cache)
            fartifact.write_artifact(base_filter_path, art.to_bytes())
            incr_counter("filter", "fleet_emit")
        except Exception as err:
            incr_counter("filter", "fleet_emit_error")
            print(f"fleet filter emission failed: "
                  f"{type(err).__name__}: {err}", file=sys.stderr)

    def refresh_serve_filter() -> None:
        """Re-arm the query plane's filter tier from the live capture
        on the same cadence the artifact is emitted (checkpoint time):
        the serve tier's cascade snapshot tracks the durable artifact,
        never drifts unboundedly behind ingest, and between refreshes
        its registry-snapshot guard forwards anything newer to the
        table-confirm tier."""
        if query_server is not None and query_server.oracle.filter_first:
            try:
                query_server.oracle.refresh_filter()
            except Exception:
                pass  # no capture yet / transient: tier stays as-is

    def publish_distribution(epoch: int) -> None:
        """Fleet-wide distribution (round 18): every epoch tick, THIS
        worker publishes the artifact at the fleet's shared path —
        the leader's merged fleet filter (written by
        leader_fleet_filter just above in the leader's own tick) —
        into its local distribution store. The bytes are
        byte-identical on every worker by the determinism contract,
        so every worker serves identical ETags/deltas/containers and
        any replica is authoritative. Best-effort per tick: a
        follower ticking before the leader's merged write lands
        publishes one epoch behind and catches up next tick."""
        if not emit_filter or query_server is None:
            return
        try:
            with open(base_filter_path, "rb") as fh:
                blob = fh.read()
        except OSError:
            return  # leader hasn't emitted yet; next epoch retries
        try:
            query_server.oracle.publish_artifact(
                epoch, blob, source="fleet")
        except Exception as err:
            print(f"filter distribution publish failed: "
                  f"{type(err).__name__}: {err}", file=sys.stderr)

    checkpoint_hook = None
    if model is not None and config.agg_state_path:
        # Snapshot device aggregates before every durable cursor write —
        # a crash must never leave the cursor ahead of aggregate state.
        def checkpoint_hook():
            sink.checkpointed_save(model.save)
            refresh_serve_filter()
    engine = LogSyncEngine(
        sink,
        database,
        num_threads=config.num_threads,
        offset=config.offset,
        limit=config.limit,
        save_period_s=parse_duration(config.save_period),
        checkpoint_hook=checkpoint_hook,
        # TPU mode streams whole responses to the native batch decoder.
        raw_batches=model is not None,
    )
    engine.start_store_threads()

    # Fleet lifecycle (ingest/fleet.py): leader election + start
    # barrier + heartbeats over the configured coordination fabric
    # (the RemoteCache for `redis`, jax.distributed for `jax`), with
    # the leader publishing checkpoint-cadence epochs every
    # `checkpointPeriod` — each worker checkpoints (aggregate snapshot
    # + cursors) when it observes the epoch advance — and a clean-
    # shutdown broadcast that stops every worker's downloaders.
    ckpt_period_s = (parse_duration(checkpoint_period)
                     if checkpoint_period else 0.0)

    def slo_state() -> tuple[dict, list]:
        """One SLO rule evaluation (telemetry/fleetobs.py): raw
        signals → (slo values, breach reasons), mirrored into the
        ``slo.*`` gauges. Cheap no-op until a threshold is set."""
        if not obs.any_slo():
            return {}, []
        snap = _metrics.get_sink().snapshot()
        ckpt_wall = fleet.last_checkpoint_wall if fleet is not None else 0.0
        f_lag = None
        if fleet is not None and query_server is not None:
            tier = getattr(query_server.oracle, "filter_tier", None)
            if tier is not None:
                f_lag = max(0, int(fleet.stats()["checkpoint_epoch"])
                            - int(tier.epoch))
        p99 = fleetobs.serve_p99_ms() if obs.max_serve_p99_ms else None
        values, degraded = fleetobs.evaluate_slos(
            obs, snap, last_checkpoint_wall=ckpt_wall,
            checkpoint_period_s=ckpt_period_s,
            filter_epoch_lag=f_lag, p99_ms=p99)
        fleetobs.publish_slo_gauges(values, degraded)
        return values, degraded

    def obs_payload() -> str:
        """The heartbeat-cadence fan-in unit: this worker's metrics
        snapshot + fleet stats + SLO state + a (wall, mono) clock
        pair, published through the coordinator fabric's TTL'd keys."""
        values, degraded = slo_state()
        return fleetobs.build_obs_payload(
            fleet_worker_id, num_workers,
            fleet_stats=fleet.stats() if fleet is not None else None,
            slo={"values": values, "degraded": degraded})

    fleet = None
    if num_workers > 1 or coord_backend or checkpoint_period:
        coordinator = build_coordinator(
            coord_backend, _cache, "ct-fetch", fleet_worker_id, num_workers)
        fleet = FleetService(
            coordinator,
            checkpoint_period_s=ckpt_period_s,
            on_checkpoint=lambda epoch: (engine.checkpoint_now(),
                                         leader_fleet_filter(),
                                         publish_distribution(epoch)),
            on_shutdown=lambda reason: (
                print(f"\nfleet shutdown broadcast: {reason}",
                      file=sys.stderr),
                engine.signal_stop(),
            ),
            obs_payload=obs_payload if obs.fleet_metrics else None,
        )

    # Flight-recorder fleet sections (round 23): a SIGUSR1/crash dump
    # from a wedged worker answers role/epoch/claims/heartbeat-age and
    # current checkpoint chain depth without a live process to query.
    def _flight_fleet() -> dict:
        return fleet.stats() if fleet is not None else {}

    def _flight_ckpt_chain() -> dict:
        agg = model.aggregator if model is not None else None
        if agg is None:
            return {}
        return {
            "chain_length": int(getattr(agg, "_ckpt_chain_len", 0)),
            "last_checkpoint_wall": (fleet.last_checkpoint_wall
                                     if fleet is not None else 0.0),
            "checkpoint_period_s": ckpt_period_s,
        }

    flight.register_section("fleet", _flight_fleet)
    flight.register_section("ckpt_chain", _flight_ckpt_chain)

    health = None
    if config.health_addr:
        try:
            health = HealthServer(
                engine, parse_duration(config.polling_delay_mean),
                addr=config.health_addr,
            )
            health.start()
        except OSError as err:
            print(f"health endpoint disabled: {err}", file=sys.stderr)
            health = None

    def healthz() -> dict:
        """The /healthz body: engine stage, last-progress timestamp,
        and the entry channel's depth."""
        updates = engine.last_updates()
        last = max(updates.values()).isoformat() if updates else None
        body = {
            "stage": run_stage["stage"],
            "last_progress": last,
            "progress": {u: {"pos": p, "end": e}
                         for u, (p, e) in engine.progress().items()},
            # Items waiting, in the unit named beside it (a get-entries
            # response an item where the sink takes raw batches); the
            # channel's bound counts entries either way.
            "entry_queue_depth": engine.entry_queue.depth(),
            "entry_queue_depth_unit": ("pages" if engine.raw_batches
                                       else "entries"),
        }
        verifier = getattr(sink, "verifier", None)
        if verifier is not None:
            # Round 17: verify-lane knobs, outcome totals, and Q-table
            # occupancy (steady state: occupancy = live log keys,
            # qtable_misses flat).
            body["verify"] = verifier.health()
        if query_server is not None:
            body["serve"] = query_server.oracle.stats()
        if fleet is not None:
            body["fleet"] = fleet.stats()
        # SLO rules (round 23): any breach renders the same body under
        # HTTP 503 (promhttp's healthy-False contract).
        values, degraded = slo_state()
        if values:
            body["slo"] = values
        if degraded:
            body["healthy"] = False
            body["degraded"] = degraded
        return body

    # Query plane: the batched membership-oracle JSON API over the live
    # aggregator (serve/server.py). TPU backend only — the oracle pins
    # epochs of the device dedup table; the per-entry database path has
    # no device table to serve.
    query_server = None
    if config.query_port and model is not None:
        from ct_mapreduce_tpu.serve.server import QueryServer

        try:
            query_server = QueryServer(
                model.aggregator, config.query_port,
                device=config.serve_device,
                replicas=config.serve_replicas,
                cache_size=config.serve_cache_size,
                # emitFilter also arms the serve plane's filter-first
                # tier and the /filter download routes (env
                # CTMR_SERVE_FILTER_FIRST can still force either way).
                filter_first=(True if emit_filter else None),
                filter_fp_rate=filter_fp,
                distrib_history=config.distrib_history,
                max_delta_chain=config.max_delta_chain).start()
            # SLO degradation flips the query plane's /healthz to 503
            # too (same rules, same reasons — satellite of round 23).
            query_server.slo_check = lambda: slo_state()[1]
            print(f"query endpoint: :{query_server.port}/query "
                  f"+ /issuer + /getcert + /filter "
                  f"(+ /filter/delta + /filter/container + "
                  f"/filter/manifest)", file=sys.stderr)
        except OSError as err:
            print(f"query endpoint disabled: {err}", file=sys.stderr)
            query_server = None
    elif config.query_port:
        print("queryPort ignored: the query plane needs backend = tpu",
              file=sys.stderr)

    metrics_server = None
    if config.metrics_port:
        # Fleet fan-in routes (round 23): any worker answers for the
        # whole fleet from the fabric's TTL'd obs payloads.
        fleet_metrics_fn = fleet_health_fn = None
        if fleet is not None and obs.fleet_metrics:
            def fleet_metrics_fn() -> str:
                return fleetobs.render_fleet_metrics(
                    fleetobs.collect_fleet_obs(fleet.fleet_obs()))

            def fleet_health_fn() -> dict:
                return fleetobs.fleet_health(
                    fleetobs.collect_fleet_obs(fleet.fleet_obs()),
                    num_workers,
                    getattr(fleet.coordinator, "liveness_timeout_s",
                            15.0))
        try:
            metrics_server = MetricsServer(
                config.metrics_port, health=healthz,
                fleet_metrics=fleet_metrics_fn,
                fleet_health=fleet_health_fn).start()
            print(f"metrics endpoint: :{metrics_server.port}/metrics "
                  f"+ /healthz"
                  + (" + /metrics/fleet + /healthz/fleet"
                     if fleet_metrics_fn else ""), file=sys.stderr)
        except OSError as err:
            print(f"metrics endpoint disabled: {err}", file=sys.stderr)
            metrics_server = None

    def handle_signal(signum, frame):
        print(f"\nsignal {signum}: stopping after current batches...",
              file=sys.stderr)
        if signum == signal.SIGTERM:
            # Orchestrator kill: leave the post-mortem artifact before
            # draining (the drain itself may be what's wedged).
            flight.dump(f"signal {signum} (SIGTERM)")
        if fleet is not None and fleet.is_leader:
            # Leader-published clean shutdown: followers observe the
            # broadcast and drain too, so one signal stops the fleet.
            fleet.request_shutdown(f"leader signal {signum}")
        engine.signal_stop()

    def handle_dump_signal(signum, frame):
        path = flight.dump(f"signal {signum} (SIGUSR1)")
        print(f"\nsignal {signum}: flight record "
              f"{path or 'not written'}", file=sys.stderr)

    # Previous handlers are restored in the finally below — main() must
    # leave no global hooks behind (same contract as the flight
    # recorder's excepthook note above): tests and runForever wrappers
    # re-enter it, and a stale handler would swallow a later SIGTERM
    # meant for the host process.
    prev_handlers = {}
    for signum, handler in ((signal.SIGINT, handle_signal),
                            (signal.SIGTERM, handle_signal)):
        prev_handlers[signum] = signal.signal(signum, handler)
    try:
        prev_handlers[signal.SIGUSR1] = signal.signal(
            signal.SIGUSR1, handle_dump_signal)
    except (AttributeError, ValueError, OSError):
        pass  # platform without SIGUSR1 / non-main thread

    printer = None
    if not config.nobars:
        printer = ProgressPrinter(
            engine, parse_duration(config.output_refresh_period)
        )
        printer.start()

    profiling = False
    if config.profile_dir:
        # SURVEY.md §5 tracing analog: a jax.profiler trace of the run
        # (device steps + host phases) next to the metric timers.
        try:
            import jax

            jax.profiler.start_trace(config.profile_dir)
            profiling = True
        except Exception as err:
            print(f"profiling disabled: {err}", file=sys.stderr)

    final_round_errors = False
    sync_round = 0
    try:
        if fleet is not None:
            # Election + start barrier: every worker begins its
            # partition at once, like the reference's Redis barrier
            # (and nobody fetches before the fleet is fully present).
            run_stage["stage"] = "electing"
            role = fleet.start(timeout_s=600.0, rejoin=resuming)
            print(f"fleet worker {fleet.worker_id}/{num_workers} "
                  f"({'leader' if role else 'follower'}"
                  f"{', rejoined' if fleet.rejoined else ''}, "
                  f"coordinator={type(fleet.coordinator).__name__})",
                  file=sys.stderr)
        while True:
            run_stage["stage"] = "syncing"
            # Dead-owner takeover on later runForever rounds (the start
            # barrier guaranteed full membership for round 0) AND on a
            # rejoining worker's first round — its logs may be mid-
            # takeover by a survivor, so it must partition against the
            # live membership (the per-log lease arbitrates the races).
            takeover = sync_round > 0 or (
                fleet is not None and fleet.rejoined)
            for url, f_off, f_lim, f_sfx in fleet_assignments(
                    fleet, log_urls, takeover=takeover,
                    errors=engine.errors):
                engine.sync_log(url, offset=f_off, limit=f_lim,
                                state_suffix=f_sfx)
            sync_round += 1
            engine.wait_for_downloads()
            run_stage["stage"] = "draining"
            engine.stop()  # drain queue, flush sink
            if model is not None:
                run_stage["stage"] = "saving"
                model.save()
                refresh_serve_filter()
                # Between rounds, nothing in flight: a table near its
                # growth threshold has the doubled table's programs
                # made ready now, so the growth compiles nothing.
                model.prepare_growth()
            if fleet is not None:
                # This round's entries are durably folded: drop the
                # fetch leases so next round's rightful owners (per the
                # then-current membership) can take them.
                fleet.release_claims()
            run_stage["stage"] = "idle"
            # Drain this round's errors so runForever doesn't re-print
            # (or unboundedly accumulate) them across polls.
            final_round_errors = bool(engine.errors)
            for e in engine.errors:
                print(f"error: {e}", file=sys.stderr)
            engine.errors.clear()
            if not config.run_forever or engine.stop_event.is_set():
                break
            if fleet is not None and fleet.shutdown_requested():
                break
            engine.start_store_threads()  # next round
            delay = polling_delay(
                parse_duration(config.polling_delay_mean),
                config.polling_delay_std_dev,
            )
            if engine.stop_event.wait(delay):
                break
    except BaseException as err:
        # The post-mortem artifact for a crashing run: spans + metric
        # snapshots as of the moment the main loop died.
        flight.dump(f"unhandled exception in ct-fetch: {err!r}")
        raise
    finally:
        if profiling:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception as err:
                # Trace serialization failures must not mask the real
                # exception or skip the remaining shutdown steps.
                print(f"profiler stop failed: {err}", file=sys.stderr)
        run_stage["stage"] = "stopped"
        if printer:
            printer.stop()
        if health:
            health.stop()
        if metrics_server:
            metrics_server.stop()
        if query_server:
            query_server.stop()
        if fleet is not None:
            fleet.stop()
        if dumper:
            dumper.stop()
        if trace.enabled():
            path = trace.export()
            if path:
                print(f"trace written to {path}", file=sys.stderr)
        flight.unregister_section("fleet")
        flight.unregister_section("ckpt_chain")
        trace.set_process_attrs(worker=None, epoch=None)
        flight.uninstall()
        for signum, prev in prev_handlers.items():
            with contextlib.suppress(ValueError, OSError):
                signal.signal(signum, prev)
        engine.cleanup()
    return 1 if final_round_errors else 0


if __name__ == "__main__":
    sys.exit(main())
