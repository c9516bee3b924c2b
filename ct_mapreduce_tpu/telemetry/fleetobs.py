"""Fleet-wide observability plane (round 23).

PR 4's telemetry (span ring, ``/metrics``, ``/healthz``) is strictly
per-process: a W-worker fleet answers "is the fleet healthy, where is
the ingest lag" only by ssh-ing into every worker. This module builds
the fleet-scoped layer out of pieces that already exist — the
coordinator fabric's TTL'd value keys, :class:`InMemSink` snapshots,
and the tracer's process attrs:

- **obs payloads**: each worker's FleetService heartbeat publishes a
  compact JSON snapshot (:func:`build_obs_payload`) — (wall, monotonic)
  clock pair, fleet stats, and the full metrics snapshot — through
  ``FleetCoordinator.publish_obs``. The payload TTL equals the
  liveness timeout, so a SIGSTOP'd worker's numbers age out on the
  same clock that marks it dead.
- **metrics fan-in**: :func:`render_fleet_metrics` renders every
  worker's payload as one Prometheus exposition — per-worker
  ``{worker="N"}`` series plus unlabeled fleet-summed counter lines,
  parity-pinned: within one response body the fleet total is exactly
  the sum of the worker-labeled lines (asserted by the smoke gate).
- **health rollup**: :func:`fleet_health` answers ``/healthz/fleet`` —
  per-worker liveness/role/heartbeat age, leader-epoch skew,
  checkpoint chain depth, and any worker's SLO degradation; a missing
  or stale worker flips the rollup unhealthy (HTTP 503).
- **SLO rules**: :func:`evaluate_slos` turns raw signals (the
  ``ingest.lag_entries.*`` gauges, checkpoint age, filter publish
  epoch lag, span-derived serve p99) into ``slo.*`` gauges with
  thresholds from the ``obs`` knob section; a breach flips the
  per-process ``/healthz`` to degraded and is visible in the rollup.
- **clock skew**: the pure correction math behind
  ``traceview --merge`` (:func:`clock_offset`,
  :func:`corrected_epoch_us`, :func:`merge_traces`) — workers publish
  (wall, monotonic) pairs through the fabric; the merger rebases every
  per-process Chrome trace onto one corrected wall timeline.

Thresholds default to 0 = disabled, so behavior is unchanged until a
deployment opts in (``sloMax*`` directives / ``CTMR_SLO_*`` envs /
platform profile ``knobs.obs``).
"""

from __future__ import annotations

import json
import time
from dataclasses import dataclass
from typing import Iterable, Optional

from ct_mapreduce_tpu.config import profile as platprofile
from ct_mapreduce_tpu.telemetry import metrics

OBS_VERSION = 1

# The knob section (platformProfile `knobs.obs`): fan-in on/off plus
# the SLO thresholds. All thresholds default to "disabled" (0) so the
# rule layer is opt-in; fleetMetrics defaults on because publishing
# rides a heartbeat that is already being sent.
_OBS_KNOBS = (
    platprofile.Knob("fleetMetrics", "CTMR_FLEET_METRICS", True,
                     parse=platprofile.parse_bool_strict,
                     env_is_set=platprofile.any_set, post=bool),
    platprofile.Knob("sloMaxIngestLag", "CTMR_SLO_MAX_INGEST_LAG", 0,
                     parse=int, is_set=platprofile.pos_int, post=int),
    platprofile.Knob("sloMaxCheckpointAge", "CTMR_SLO_MAX_CKPT_AGE_S", 0.0,
                     parse=float, is_set=platprofile.pos_float, post=float),
    platprofile.Knob("sloMaxFilterLag", "CTMR_SLO_MAX_FILTER_LAG", 0,
                     parse=int, is_set=platprofile.pos_int, post=int),
    platprofile.Knob("sloMaxServeP99Ms", "CTMR_SLO_MAX_SERVE_P99_MS", 0.0,
                     parse=float, is_set=platprofile.pos_float, post=float),
)


@dataclass(frozen=True)
class ObsKnobs:
    fleet_metrics: bool
    max_ingest_lag: int
    max_ckpt_age_s: float
    max_filter_lag: int
    max_serve_p99_ms: float

    def any_slo(self) -> bool:
        return bool(self.max_ingest_lag or self.max_ckpt_age_s
                    or self.max_filter_lag or self.max_serve_p99_ms)


def resolve_obs(fleet_metrics=None, max_ingest_lag=None,
                max_ckpt_age_s=None, max_filter_lag=None,
                max_serve_p99_ms=None) -> ObsKnobs:
    """The ``obs`` section through the platformProfile ladder
    (explicit > CTMR_* env > profile > default)."""
    knobs = platprofile.resolve_section("obs", _OBS_KNOBS, {
        "fleetMetrics": fleet_metrics,
        "sloMaxIngestLag": max_ingest_lag,
        "sloMaxCheckpointAge": max_ckpt_age_s,
        "sloMaxFilterLag": max_filter_lag,
        "sloMaxServeP99Ms": max_serve_p99_ms,
    })
    return ObsKnobs(
        fleet_metrics=knobs["fleetMetrics"],
        max_ingest_lag=knobs["sloMaxIngestLag"],
        max_ckpt_age_s=knobs["sloMaxCheckpointAge"],
        max_filter_lag=knobs["sloMaxFilterLag"],
        max_serve_p99_ms=knobs["sloMaxServeP99Ms"],
    )


# -- clock pairs + skew correction ---------------------------------------


def clock_pair() -> dict:
    """One (wall, monotonic) sample, read back to back — the unit of
    the coordinator-fabric timestamp exchange."""
    return {"wall": time.time(), "mono": time.monotonic()}


def clock_offset(pair: dict) -> float:
    """wall = mono + offset for the process that published ``pair``.
    On one host the monotonic clock is system-wide (per boot), so two
    processes' offsets differ only by their wall-read jitter; across
    hosts the fabric exchange carries each machine's own offset."""
    return float(pair["wall"]) - float(pair["mono"])


def corrected_epoch_us(ts_us: float, mono_t0: float,
                       offset: float) -> float:
    """A trace event timestamp (µs since the tracer's perf_counter
    base, anchored at ``mono_t0`` on the monotonic clock) → absolute
    wall-epoch µs via that process's clock offset."""
    return (mono_t0 + offset) * 1e6 + float(ts_us)


def _doc_offset(doc: dict, pairs: Optional[dict]) -> float:
    """The clock offset for one exported trace doc: the fabric pair
    for its worker when one was exchanged, else the (wall_t0, mono_t0)
    pair the tracer itself sampled at startup."""
    other = doc.get("otherData", {})
    if pairs:
        attrs = other.get("process_attrs", {}) or {}
        worker = attrs.get("worker")
        if worker is not None and worker in pairs:
            return clock_offset(pairs[worker])
        if str(worker) in pairs:
            return clock_offset(pairs[str(worker)])
    return (float(other.get("wall_t0", 0.0))
            - float(other.get("mono_t0", 0.0)))


def merge_traces(docs: Iterable[dict],
                 pairs: Optional[dict] = None) -> dict:
    """Stitch per-process Chrome-trace docs into ONE timeline.

    Each doc's events are shifted onto the corrected wall clock
    (fabric ``pairs`` keyed by worker id when available, the doc's own
    startup pair otherwise), then the whole timeline is rebased so the
    earliest event sits at ts=0 — Perfetto renders one run, clock skew
    gone. Process metadata events name each track by worker/pid."""
    docs = list(docs)
    shifted: list[tuple[dict, float, dict]] = []
    t_min: Optional[float] = None
    for doc in docs:
        other = doc.get("otherData", {})
        mono_t0 = float(other.get("mono_t0",
                                  other.get("wall_t0", 0.0)))
        offset = _doc_offset(doc, pairs)
        base_us = corrected_epoch_us(0.0, mono_t0, offset)
        shifted.append((doc, base_us, other))
        for ev in doc.get("traceEvents", []):
            if "ts" in ev:
                t = base_us + float(ev["ts"])
                t_min = t if t_min is None else min(t_min, t)
    if t_min is None:
        t_min = 0.0
    events: list[dict] = []
    for doc, base_us, other in shifted:
        pid = other.get("pid", 0)
        attrs = other.get("process_attrs", {}) or {}
        worker = attrs.get("worker")
        label = (f"worker {worker} (pid {pid})"
                 if worker is not None else f"pid {pid}")
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"name": label}})
        for ev in doc.get("traceEvents", []):
            ev = dict(ev)
            if "ts" in ev:
                ev["ts"] = base_us + float(ev["ts"]) - t_min
            events.append(ev)
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "merged_from": len(docs),
            "epoch_us_at_ts0": t_min,
            "skew_corrected": bool(pairs),
        },
    }


# -- obs payloads (the fabric fan-in unit) -------------------------------


def build_obs_payload(worker_id: int, num_workers: int,
                      fleet_stats: Optional[dict] = None,
                      slo: Optional[dict] = None,
                      sink=None) -> str:
    """One worker's heartbeat-cadence snapshot as compact JSON: clock
    pair (the traceview skew exchange rides the same key), fleet
    stats, SLO state, and the full metrics snapshot."""
    s = sink if sink is not None else metrics.get_sink()
    snap_fn = getattr(s, "snapshot", None)
    doc = {
        "v": OBS_VERSION,
        "worker": int(worker_id),
        "num_workers": int(num_workers),
        "wall": time.time(),
        "mono": time.monotonic(),
        "metrics": snap_fn() if snap_fn is not None else {},
    }
    if fleet_stats:
        doc["fleet"] = fleet_stats
    if slo:
        doc["slo"] = slo
    payload = json.dumps(doc, sort_keys=True, separators=(",", ":"))
    metrics.set_gauge("fleetobs", "payload_bytes",
                      value=float(len(payload)))
    return payload


def parse_obs_payload(raw: str) -> Optional[dict]:
    """Tolerant decode: a corrupt/foreign payload in the fabric must
    degrade to "worker not reporting", never crash the scrape."""
    try:
        doc = json.loads(raw)
    except (TypeError, ValueError):
        return None
    if not isinstance(doc, dict) or not isinstance(
            doc.get("metrics", {}), dict):
        return None
    if doc.get("v", OBS_VERSION) != OBS_VERSION:
        return None
    return doc


def collect_fleet_obs(raw_payloads: dict) -> dict:
    """``coordinator.fleet_obs()`` output → {worker_id: parsed doc},
    dropping anything unparseable."""
    out: dict = {}
    for wid, raw in sorted(raw_payloads.items()):
        doc = parse_obs_payload(raw)
        if doc is not None:
            out[int(wid)] = doc
    return out


def clock_pairs_from_obs(payloads: dict) -> dict:
    """The traceview skew exchange: worker id → (wall, mono) pair."""
    pairs = {}
    for wid, doc in payloads.items():
        if "wall" in doc and "mono" in doc:
            pairs[int(wid)] = {"wall": doc["wall"], "mono": doc["mono"]}
    return pairs


# -- metrics fan-in ------------------------------------------------------


def render_fleet_metrics(payloads: dict) -> str:
    """Every worker's snapshot as ONE Prometheus exposition.

    Counters render one ``{worker="N"}`` series per reporting worker
    plus an unlabeled fleet-summed line — the parity pin: within this
    body, ``metric == sum(metric{worker=...})`` exactly (same floats,
    summed here, no re-scrape race). Gauges and sample summaries are
    per-worker only: summing gauges across workers is meaningless.
    """
    from ct_mapreduce_tpu.telemetry.promhttp import _fmt, metric_name

    workers = sorted(payloads)
    lines: list[str] = []

    counter_keys: set = set()
    gauge_keys: set = set()
    sample_keys: set = set()
    for wid in workers:
        snap = payloads[wid].get("metrics", {})
        counter_keys.update(snap.get("counters", {}))
        gauge_keys.update(snap.get("gauges", {}))
        sample_keys.update(snap.get("samples", {}))

    for key in sorted(counter_keys):
        name = metric_name(key)
        lines.append(f"# TYPE {name} counter")
        total = 0.0
        for wid in workers:
            vals = payloads[wid].get("metrics", {}).get("counters", {})
            if key in vals:
                total += float(vals[key])
                lines.append(f'{name}{{worker="{wid}"}} {_fmt(vals[key])}')
        lines.append(f"{name} {_fmt(total)}")
    for key in sorted(gauge_keys):
        name = metric_name(key)
        lines.append(f"# TYPE {name} gauge")
        for wid in workers:
            vals = payloads[wid].get("metrics", {}).get("gauges", {})
            if key in vals:
                lines.append(f'{name}{{worker="{wid}"}} {_fmt(vals[key])}')
    for key in sorted(sample_keys):
        name = metric_name(key)
        lines.append(f"# TYPE {name} summary")
        for wid in workers:
            s = payloads[wid].get("metrics", {}).get("samples", {})
            if key not in s:
                continue
            s = s[key]
            for q, field in (("0.5", "p50"), ("0.95", "p95"),
                             ("0.99", "p99")):
                if field in s:
                    lines.append(f'{name}{{worker="{wid}",quantile="{q}"}}'
                                 f' {_fmt(s[field])}')
            lines.append(f'{name}_sum{{worker="{wid}"}} {_fmt(s["sum"])}')
            lines.append(
                f'{name}_count{{worker="{wid}"}} {_fmt(s["count"])}')
    metrics.set_gauge("fleetobs", "workers_reporting",
                      value=float(len(workers)))
    return "\n".join(lines) + "\n"


def fleet_counter_parity(body: str) -> list[str]:
    """Parity check over one rendered exposition body: every unlabeled
    counter line must equal the sum of its ``{worker=...}`` lines.
    Returns the violating metric names (empty = parity holds) — the
    smoke gate's assertion, usable against a live scrape."""
    import re

    worker_re = re.compile(r'^([a-zA-Z0-9_:]+)\{worker="\d+"\} (\S+)$')
    total_re = re.compile(r"^([a-zA-Z0-9_:]+) (\S+)$")
    counters: set = set()
    sums: dict = {}
    totals: dict = {}
    cur_type = ""
    for line in body.splitlines():
        if line.startswith("# TYPE "):
            parts = line.split()
            cur_type = parts[3] if len(parts) >= 4 else ""
            if cur_type == "counter":
                counters.add(parts[2])
            continue
        m = worker_re.match(line)
        if m and m.group(1) in counters:
            sums[m.group(1)] = sums.get(m.group(1), 0.0) + float(m.group(2))
            continue
        m = total_re.match(line)
        if m and m.group(1) in counters:
            totals[m.group(1)] = float(m.group(2))
    return sorted(
        name for name, total in totals.items()
        if abs(total - sums.get(name, 0.0)) > 1e-9 * max(1.0, abs(total)))


# -- SLO rules -----------------------------------------------------------


def serve_p99_ms(tracer=None) -> Optional[float]:
    """Span-derived serve p99: the p99 of ``serve.wait`` span
    durations currently in the trace ring (the full submit→reply wait
    each client saw), in milliseconds. None when tracing is off or no
    serve spans landed yet."""
    if tracer is None:
        from ct_mapreduce_tpu.telemetry import trace

        tracer = trace.get_tracer()
    if tracer is None:
        return None
    durs = sorted(float(ev.get("dur", 0.0))
                  for ev in tracer.events()
                  if ev.get("ph") == "X" and ev.get("name") == "serve.wait")
    if not durs:
        return None
    idx = min(len(durs) - 1, int(0.99 * (len(durs) - 1) + 0.5))
    return durs[idx] / 1000.0


def evaluate_slos(knobs: ObsKnobs, snap: Optional[dict] = None, *,
                  now: Optional[float] = None,
                  last_checkpoint_wall: float = 0.0,
                  checkpoint_period_s: float = 0.0,
                  filter_epoch_lag: Optional[int] = None,
                  p99_ms: Optional[float] = None) -> tuple[dict, list]:
    """Raw signals → (slo values, breach reasons).

    Pure given its inputs (timestamps and snapshot passed in), so the
    threshold edges unit-test exactly. Signals:

    - ingest lag: max over the ``ingest.lag_entries.*`` gauges in
      ``snap`` (cursor vs STH tree head, worst log wins)
    - checkpoint age: ``now - last_checkpoint_wall`` — only once a
      first checkpoint exists, and graded against
      ``max(sloMaxCheckpointAge, checkpoint period)`` so a threshold
      tighter than the cadence can't flap
    - filter publish epoch lag: caller-computed (checkpoint epoch vs
      the serve tier's published filter epoch)
    - serve p99: span-derived (:func:`serve_p99_ms`), milliseconds
    """
    now = time.time() if now is None else now
    values: dict = {}
    degraded: list = []

    if snap is not None:
        lags = [float(v) for k, v in snap.get("gauges", {}).items()
                if k.startswith("ingest.lag_entries.")]
        if lags:
            values["ingest_lag_entries"] = max(lags)
            if (knobs.max_ingest_lag
                    and values["ingest_lag_entries"] > knobs.max_ingest_lag):
                degraded.append(
                    f"ingest_lag {values['ingest_lag_entries']:.0f} > "
                    f"{knobs.max_ingest_lag}")

    if last_checkpoint_wall > 0:
        age = max(0.0, now - last_checkpoint_wall)
        values["checkpoint_age_s"] = age
        limit = max(knobs.max_ckpt_age_s, checkpoint_period_s)
        if knobs.max_ckpt_age_s and age > limit:
            degraded.append(f"checkpoint_age {age:.1f}s > {limit:.1f}s")

    if filter_epoch_lag is not None:
        values["filter_epoch_lag"] = float(filter_epoch_lag)
        if knobs.max_filter_lag and filter_epoch_lag > knobs.max_filter_lag:
            degraded.append(
                f"filter_epoch_lag {filter_epoch_lag} > "
                f"{knobs.max_filter_lag}")

    if p99_ms is not None:
        values["serve_p99_ms"] = float(p99_ms)
        if knobs.max_serve_p99_ms and p99_ms > knobs.max_serve_p99_ms:
            degraded.append(
                f"serve_p99 {p99_ms:.2f}ms > {knobs.max_serve_p99_ms}ms")

    return values, degraded


def publish_slo_gauges(values: dict, degraded: list) -> None:
    """Mirror one SLO evaluation into ``slo.*`` gauges so scrapes (and
    the fan-in) carry the derived signals, not just the raw ones."""
    for key, val in values.items():
        metrics.set_gauge("slo", key, value=float(val))
    metrics.set_gauge("slo", "degraded",
                      value=1.0 if degraded else 0.0)


# -- health rollup -------------------------------------------------------


def fleet_health(payloads: dict, num_workers: int,
                 liveness_timeout_s: float, *,
                 now: Optional[float] = None) -> dict:
    """The ``/healthz/fleet`` body: every worker's liveness, role,
    heartbeat age, epoch, claims, and SLO state, plus the rollup
    verdict. Degraded (``healthy: False``) when any expected worker is
    missing/stale, leader epochs disagree beyond one tick (a worker
    still observing epoch N-1 mid-propagation is normal), no leader is
    reporting, or any worker reports SLO breaches."""
    now = time.time() if now is None else now
    workers: dict = {}
    degraded: list = []
    epoch_of: dict = {}
    leaders = 0
    for wid, doc in sorted(payloads.items()):
        fleet = doc.get("fleet", {}) or {}
        age = max(0.0, now - float(doc.get("wall", 0.0)))
        entry = {
            "role": fleet.get("role", "unknown"),
            "age_s": round(age, 3),
            "epoch": fleet.get("checkpoint_epoch"),
            "claims": fleet.get("claims", []),
            "checkpoints_run": fleet.get("checkpoints_run"),
            "slo_degraded": list(doc.get("slo", {}).get("degraded", [])),
        }
        workers[str(wid)] = entry
        if entry["role"] == "leader":
            leaders += 1
        if entry["epoch"] is not None:
            epoch_of[wid] = int(entry["epoch"])
        if age > liveness_timeout_s:
            degraded.append(f"worker {wid} stale ({age:.1f}s)")
        for reason in entry["slo_degraded"]:
            degraded.append(f"worker {wid} slo: {reason}")
    missing = sorted(set(range(num_workers)) - set(payloads))
    for wid in missing:
        degraded.append(f"worker {wid} not reporting")
    epochs = epoch_of.values()
    epoch_skew = (max(epochs) - min(epochs)) if epochs else 0
    if epoch_skew > 1:
        # Name who lags: a reason that blames nobody sends the operator
        # through every worker's page.
        behind = ", ".join(f"worker {wid}" for wid, e in sorted(
            epoch_of.items()) if e == min(epochs))
        degraded.append(f"leader-epoch skew {epoch_skew} ({behind} behind)")
    if payloads and leaders == 0:
        degraded.append("no leader reporting")
    chain_depths = {
        str(wid): doc.get("metrics", {}).get("gauges", {}).get(
            "ckpt.chain_length")
        for wid, doc in payloads.items()
        if doc.get("metrics", {}).get("gauges", {}).get(
            "ckpt.chain_length") is not None
    }
    body = {
        "healthy": not degraded,
        "num_workers": num_workers,
        "workers_reporting": len(payloads),
        "missing": missing,
        "workers": workers,
        "leader_epoch_skew": epoch_skew,
        "ckpt_chain_depth": chain_depths,
        "liveness_timeout_s": liveness_timeout_s,
    }
    if degraded:
        body["degraded"] = degraded
    return body
