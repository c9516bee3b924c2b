"""Local multi-worker ingest fleet harness (CPU-verifiable).

Drives the real pod-scale path end to end on one machine: W
`ct-fetch` worker PROCESSES (the actual `cmd/ct_fetch.py` main, fleet
directives and all) coordinate through an in-process miniredis —
leader election, start barrier, heartbeats, leader-published
checkpoint epochs — over disjoint rendezvous partitions of a shared
deterministic fakelog fixture, then the per-worker aggregate
checkpoints merge (`agg/merge.py`) into one storage-statistics view
that is compared against a single-process serial run of the same
entries.

    python tools/fleet.py --workers 2 --logs 4 --entries-per-log 256

Child mode (`--child`) is one worker process; the parent (and
tests/test_multiprocess.py) spawns it. A child
killed mid-run (SIGKILL) and respawned resumes from its checkpoint
cursor in miniredis — the warm-restart contract — which the
kill-and-resume test drives directly.
"""

from __future__ import annotations

import argparse
import base64
import json
import os
import subprocess
import sys
import time
from pathlib import Path
from urllib.parse import parse_qs, urlparse

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


# -- deterministic fixture ----------------------------------------------


def build_fixture(path: str, n_logs: int = 2, entries_per_log: int = 128,
                  dupes: int = 8, max_batch: int = 256,
                  shared_issuer: bool = True) -> dict:
    """A wire-faithful multi-log corpus (utils/minicert — dependency-
    free canonical DER): per-log disjoint serial ranges (so partitions
    never share a certificate identity — see agg/merge.py's honest-
    limit note), intra-log duplicate serials (dedup exercised inside a
    partition), per-log issuers with CRL DPs, plus one issuer SHARED
    across logs (the cross-worker registry-merge case). JSON on disk
    so subprocess workers rebuild the exact same transport."""
    from ct_mapreduce_tpu.ingest import leaf as leaflib
    from ct_mapreduce_tpu.utils import minicert

    logs: dict[str, list[dict]] = {}
    shared_der = minicert.make_cert(
        serial=7, issuer_cn="Fleet Shared CA", is_ca=True)
    for li in range(n_logs):
        url = f"https://ct.example.com/fleet{li}"
        issuer_der = minicert.make_cert(
            serial=2 + li, issuer_cn=f"Fleet CA {li}", is_ca=True)
        entries = []
        for e in range(entries_per_log):
            # Tail entries replay early serials: duplicates within the
            # partition (deduped on device) without crossing logs.
            serial = (1000 + li * 1_000_000
                      + (e % (entries_per_log - dupes)
                         if dupes and entries_per_log > dupes else e))
            use_shared = shared_issuer and e % 5 == 0
            cert = minicert.make_cert(
                serial=serial,
                issuer_cn=("Fleet Shared CA" if use_shared
                           else f"Fleet CA {li}"),
                subject_cn=f"w{li}-{e}.fleet.example",
                crl_dps=(f"http://crl.example/fleet{li}.crl",),
            )
            li_b = leaflib.encode_leaf_input(
                cert, timestamp_ms=1_700_000_000_000 + e)
            ed_b = leaflib.encode_extra_data(
                [shared_der if use_shared else issuer_der])
            entries.append({
                "leaf_input": base64.b64encode(li_b).decode(),
                "extra_data": base64.b64encode(ed_b).decode(),
            })
        logs[url] = entries
    fixture = {"max_batch": max_batch, "logs": logs}
    with open(path, "w") as fh:
        json.dump(fixture, fh)
    return fixture


class FixtureTransport:
    """The injectable HTTP transport over a fixture dict: answers
    get-sth / get-entries for every fixture log, like tests/fakelog
    but multi-log and subprocess-reconstructible. ``throttle_ms``
    delays each get-entries response — paces the download so
    checkpoint epochs land mid-ingest (the kill-window the resume
    tests need)."""

    def __init__(self, fixture: dict, throttle_ms: float = 0.0):
        self.logs = {
            urlparse(url).path: entries
            for url, entries in fixture["logs"].items()
        }
        self.max_batch = int(fixture.get("max_batch", 256))
        self.throttle_ms = float(throttle_ms)
        # get-entries start indices served, in order (resume evidence:
        # a warm restart's first fetch is the checkpoint cursor, not 0).
        self.entry_requests: list[int] = []

    def __call__(self, url: str) -> tuple[int, dict, bytes]:
        parsed = urlparse(url)
        path = parsed.path
        for prefix, entries in self.logs.items():
            if not path.startswith(prefix + "/"):
                continue
            if path.endswith("/ct/v1/get-sth"):
                return 200, {}, json.dumps(
                    {"tree_size": len(entries),
                     "timestamp": 1_700_000_000_000}).encode()
            if path.endswith("/ct/v1/get-entries"):
                if self.throttle_ms:
                    time.sleep(self.throttle_ms / 1000.0)
                q = parse_qs(parsed.query)
                start = int(q["start"][0])
                self.entry_requests.append(start)
                end = min(int(q["end"][0]), start + self.max_batch - 1,
                          len(entries) - 1)
                if start >= len(entries):
                    return 400, {}, b"range beyond tree size"
                return 200, {}, json.dumps(
                    {"entries": entries[start:end + 1]}).encode()
            return 404, {}, b"not found"
        return 404, {}, b"unknown log"


def install_transport(fixture: dict, throttle_ms: float = 0.0) -> None:
    """Route CTLogClient's default transport to the fixture."""
    from ct_mapreduce_tpu.ingest import ctclient

    ctclient._default_transport = FixtureTransport(fixture, throttle_ms)


# -- snapshots -----------------------------------------------------------


def snapshot_jsonable(snap) -> dict:
    """Canonical JSON form of an AggregateSnapshot — the byte-
    comparable parity object (sorted keys, sets → sorted lists)."""
    return {
        "counts": {f"{iss}|{exp}": n
                   for (iss, exp), n in sorted(snap.counts.items())},
        "crls": {iss: sorted(v) for iss, v in sorted(snap.crls.items())},
        "dns": {iss: sorted(v) for iss, v in sorted(snap.dns.items())},
        "total": snap.total,
        "verified": dict(sorted(snap.verified.items())),
        "failed": dict(sorted(snap.failed.items())),
    }


def merged_snapshot(state_paths: list[str]) -> dict:
    from ct_mapreduce_tpu.agg import merge

    return snapshot_jsonable(merge.load_checkpoints(state_paths).drain())


def filter_bytes(state_paths: list[str], fp_rate: float = 0.01) -> bytes:
    """The filter artifact compiled from one or many checkpoints —
    the byte-comparable parity object of the round-15 determinism
    contract: a W-worker fleet's merged filter must equal the serial
    run's bit for bit (canonical keys hash under sorted-issuerID
    ordinals, so worker-local registry numbering cancels out)."""
    from ct_mapreduce_tpu.agg import merge
    from ct_mapreduce_tpu.filter import build_from_merged

    merged = merge.load_checkpoints(state_paths)
    return build_from_merged(merged, fp_rate=fp_rate).to_bytes()


# -- one worker process --------------------------------------------------


def write_worker_ini(path: str, fixture: dict, state_path: str,
                     redis_addr: str = "", worker_id: int = 0,
                     num_workers: int = 1, checkpoint_period: str = "",
                     batch_size: int = 64, table_bits: int = 12,
                     coordinator: str = "", emit_filter: bool = True,
                     query_port: int = 0,
                     run_forever: bool = False,
                     trace_path: str = "",
                     metrics_port: int = 0,
                     extra_lines: tuple = ()) -> None:
    lines = [
        f"logList = {','.join(fixture['logs'])}",
        "backend = tpu",
        f"batchSize = {batch_size}",
        f"tableBits = {table_bits}",
        "meshShape = shard:1",
        f"aggStatePath = {state_path}",
        "healthAddr = ",
        "nobars = true",
        "savePeriod = 15m",
    ]
    if emit_filter:
        # Filter capture in every harness checkpoint (round 15): the
        # --verify path builds the merged fleet filter from the worker
        # snapshots and byte-compares it against the serial run's.
        lines += ["emitFilter = true", "filterFpRate = 0.01"]
    if redis_addr:
        lines.append(f"redisHost = {redis_addr}")
    if num_workers > 1 or coordinator:
        lines += [
            f"numWorkers = {num_workers}",
            f"workerId = {worker_id}",
            f"coordinatorBackend = {coordinator or 'redis'}",
        ]
    if checkpoint_period:
        lines.append(f"checkpointPeriod = {checkpoint_period}")
    if query_port:
        # The live-storm leg (tools/pullstorm.py --live-fleet) pulls
        # /filter + /filter/delta from the WORKERS themselves while
        # they ingest; a deep distribution history keeps lagging
        # clients on the delta path for the whole leg.
        # Deep history + chain budget: every epoch the leg captures
        # stays delta-servable (no mid-leg anchors/evictions), so the
        # failover-straddling span is always a pure chain replay.
        lines += [f"queryPort = {query_port}", "distribHistory = 128",
                  "maxDeltaChain = 128"]
    if run_forever:
        lines += ["runForever = true", "pollingDelayMean = 1s",
                  "pollingDelayStdDev = 0"]
    if trace_path:
        # Per-worker span ring (round 23): the obs smoke merges these
        # into one skew-corrected timeline (traceview --merge).
        lines.append(f"tracePath = {trace_path}")
    if metrics_port:
        lines.append(f"metricsPort = {metrics_port}")
    lines += list(extra_lines)
    with open(path, "w") as fh:
        fh.write("\n".join(lines) + "\n")


def read_cursors(redis_addr: str, fixture: dict,
                 num_workers: int = 1) -> dict[str, int]:
    """Durable per-log (and per-stripe) cursor positions from the
    shared cache — the warm-restart evidence."""
    from ct_mapreduce_tpu.ingest.ctclient import short_url
    from ct_mapreduce_tpu.storage.rediscache import RedisCache

    cache = RedisCache(redis_addr)
    out: dict[str, int] = {}
    try:
        for url in fixture["logs"]:
            keys = [short_url(url)]
            keys += [f"{short_url(url)}#w{w}" for w in range(num_workers)]
            for key in keys:
                state = cache.load_log_state(key)
                if state is not None:
                    out[key] = state.max_entry
    finally:
        cache.close()
    return out


def child_main(args) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from ct_mapreduce_tpu.utils import compile_cache

    compile_cache.configure()
    with open(args.fixture) as fh:
        fixture = json.load(fh)
    install_transport(fixture, throttle_ms=args.throttle_ms)

    # Resume evidence BEFORE the run: the durable cursors this worker
    # will start from (0 on a cold start; the checkpoint position on a
    # warm restart). Printed first so a later SIGKILL can't lose it.
    resume = read_cursors(args.redis, fixture, args.workers) \
        if args.redis else {}
    print("FLEET-CHILD " + json.dumps(
        {"event": "start", "worker": args.worker_id,
         "resume_cursors": resume}), flush=True)

    ini = os.path.join(args.state_dir, f"worker{args.worker_id}.ini")
    state_path = os.path.join(args.state_dir, "agg.npz")
    write_worker_ini(
        ini, fixture, state_path, redis_addr=args.redis,
        worker_id=args.worker_id, num_workers=args.workers,
        checkpoint_period=args.checkpoint_period,
        batch_size=args.batch_size, table_bits=args.table_bits,
        coordinator=args.coordinator, query_port=args.query_port,
        run_forever=args.run_forever,
        trace_path=args.trace_path, metrics_port=args.metrics_port,
        extra_lines=tuple(args.ini_line or ()),
    )
    from ct_mapreduce_tpu.cmd import ct_fetch
    from ct_mapreduce_tpu.ingest.fleet import (
        partition_logs,
        worker_state_path,
    )

    t0 = time.monotonic()
    rc = ct_fetch.main(["-config", ini, "-nobars"])
    wall = time.monotonic() - t0

    urls = list(fixture["logs"])
    mine = (urls if args.workers <= 1 or len(urls) == 1
            else partition_logs(urls, args.worker_id, args.workers))
    print("FLEET-CHILD " + json.dumps({
        "event": "done", "worker": args.worker_id, "rc": rc,
        "wall_s": round(wall, 3),
        "owned_logs": mine,
        "state_path": worker_state_path(
            state_path, args.worker_id, args.workers),
    }), flush=True)
    return rc


# -- the parent orchestration -------------------------------------------


def spawn_worker(worker_id: int, workers: int, fixture_path: str,
                 state_dir: str, redis_addr: str,
                 checkpoint_period: str = "", batch_size: int = 64,
                 table_bits: int = 12, throttle_ms: float = 0.0,
                 coordinator: str = "",
                 compile_cache: bool = True,
                 compile_cache_readonly: bool = False,
                 query_port: int = 0,
                 run_forever: bool = False,
                 trace_path: str = "",
                 metrics_port: int = 0,
                 ini_lines: tuple = (),
                 extra_env: dict = None) -> subprocess.Popen:
    """Spawn one worker process, pinned to the CPU: a chip belongs to
    one process, so W workers on chips would need W chips.

    Pass ``compile_cache=False`` (the child runs with
    ``JAX_ENABLE_COMPILATION_CACHE=false``) for every process involved
    in a kill-and-resume sequence. Observed on this jax/XLA CPU build:
    when the restarted worker shares a persistent compilation cache,
    its native heap intermittently corrupts — XLA ``Check failed:
    allocation.size() == ...`` / ``is_tuple_`` aborts, glibc
    ``corrupted size vs. prev_size``, or (worst) a clean exit whose
    checkpointed table rows are recycled-heap garbage. The trigger
    wasn't fully pinned (a read-only cache for the victim did not
    clear it; a clean no-kill restart never reproduces), but cache
    exclusion is the configuration repeatedly validated
    corruption-free. ``compile_cache_readonly=True`` (consume entries,
    never write one) remains for processes that only need protection
    against truncated-entry WRITES."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("CT_TPU_TESTS", None)
    if not compile_cache:
        env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    if compile_cache_readonly:
        # No compile is ever slow enough to be worth writing.
        env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "1e9"
    env["PYTHONPATH"] = str(REPO)
    argv = [
        sys.executable, str(Path(__file__).resolve()), "--child",
        "--worker-id", str(worker_id), "--workers", str(workers),
        "--fixture", fixture_path, "--state-dir", state_dir,
        "--redis", redis_addr,
        "--batch-size", str(batch_size), "--table-bits", str(table_bits),
        "--throttle-ms", str(throttle_ms),
    ]
    if checkpoint_period:
        argv += ["--checkpoint-period", checkpoint_period]
    if coordinator:
        argv += ["--coordinator", coordinator]
    if query_port:
        argv += ["--query-port", str(query_port)]
    if run_forever:
        argv += ["--run-forever"]
    if trace_path:
        argv += ["--trace-path", trace_path]
    if metrics_port:
        argv += ["--metrics-port", str(metrics_port)]
    for line in ini_lines:
        argv += ["--ini-line", line]
    if extra_env:
        env.update(extra_env)
    return subprocess.Popen(argv, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, env=env)


def child_events(output: str) -> list[dict]:
    return [json.loads(line.split(" ", 1)[1])
            for line in output.splitlines()
            if line.startswith("FLEET-CHILD ")]


def run_serial_reference(fixture: dict, state_dir: str,
                         batch_size: int = 64,
                         table_bits: int = 12) -> dict:
    """The single-worker truth, computed in-process (no fleet
    directives, in-process mock cache): the parity target."""
    from ct_mapreduce_tpu.agg.aggregator import HostSnapshotAggregator
    from ct_mapreduce_tpu.cmd import ct_fetch
    from ct_mapreduce_tpu.ingest import ctclient

    ini = os.path.join(state_dir, "serial.ini")
    state = os.path.join(state_dir, "serial.npz")
    write_worker_ini(ini, fixture, state)
    orig_transport = ctclient._default_transport
    install_transport(fixture)
    try:
        rc = ct_fetch.main(["-config", ini, "-nobars"])
    finally:
        ctclient._default_transport = orig_transport
    if rc != 0:
        raise RuntimeError(f"serial reference run failed rc={rc}")
    agg = HostSnapshotAggregator(capacity=1 << 10)
    agg.load_checkpoint(state)
    return snapshot_jsonable(agg.drain())


def run_fleet(workers: int = 2, n_logs: int = 4, entries_per_log: int = 256,
              dupes: int = 16, max_batch: int = 256, state_dir: str = "",
              checkpoint_period: str = "", batch_size: int = 64,
              table_bits: int = 12, throttle_ms: float = 0.0,
              verify: bool = False, coordinator: str = "") -> dict:
    """Spawn a W-worker fleet over a fresh fixture; returns the
    summary dict (aggregate entries/s, per-worker walls, merged
    snapshot, optional serial-parity verdict)."""
    import tempfile

    from ct_mapreduce_tpu.utils.miniredis import MiniRedis

    state_dir = state_dir or tempfile.mkdtemp(prefix="ct-fleet-")
    os.makedirs(state_dir, exist_ok=True)
    fixture_path = os.path.join(state_dir, "fixture.json")
    fixture = build_fixture(
        fixture_path, n_logs=n_logs, entries_per_log=entries_per_log,
        dupes=dupes, max_batch=max_batch)
    total_entries = sum(len(v) for v in fixture["logs"].values())

    redis = MiniRedis().start()
    try:
        t0 = time.monotonic()
        procs = [
            spawn_worker(
                w, workers, fixture_path,
                os.path.join(state_dir, f"w{w}"), redis.address,
                checkpoint_period=checkpoint_period,
                batch_size=batch_size, table_bits=table_bits,
                throttle_ms=throttle_ms, coordinator=coordinator)
            for w in range(workers)
        ]
        outs = [p.communicate(timeout=600)[0] for p in procs]
        wall = time.monotonic() - t0
    finally:
        redis.stop()
    for w, (p, out) in enumerate(zip(procs, outs)):
        if p.returncode != 0:
            raise RuntimeError(f"worker {w} failed rc={p.returncode}:\n{out}")
    events = [child_events(out) for out in outs]
    dones = [next(e for e in evs if e["event"] == "done") for evs in events]
    state_paths = [d["state_path"] for d in dones]
    merged = merged_snapshot(state_paths)
    result = {
        "workers": workers,
        "logs": n_logs,
        "entries": total_entries,
        "wall_s": round(wall, 3),
        "entries_per_s": round(total_entries / wall, 1),
        "worker_walls_s": [d["wall_s"] for d in dones],
        "owned_logs": {d["worker"]: d["owned_logs"] for d in dones},
        "merged_total": merged["total"],
        "state_paths": state_paths,
        "state_dir": state_dir,
    }
    if verify:
        ref = run_serial_reference(
            fixture, state_dir, batch_size=batch_size,
            table_bits=table_bits)
        result["parity"] = int(merged == ref)
        if merged != ref:
            result["merged"] = merged
            result["reference"] = ref
        # Round-15 artifact determinism: merged fleet filter ==
        # serial-run filter, byte for byte.
        fleet_blob = filter_bytes(state_paths)
        serial_blob = filter_bytes(
            [os.path.join(state_dir, "serial.npz")])
        result["filter_parity"] = int(fleet_blob == serial_blob)
        result["filter_bytes"] = len(fleet_blob)
        if fleet_blob != serial_blob:
            result["filter_bytes_serial"] = len(serial_blob)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--worker-id", type=int, default=0)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--fixture", default="")
    ap.add_argument("--state-dir", default="")
    ap.add_argument("--redis", default="")
    ap.add_argument("--checkpoint-period", default="")
    ap.add_argument("--coordinator", default="")
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--table-bits", type=int, default=12)
    ap.add_argument("--throttle-ms", type=float, default=0.0)
    ap.add_argument("--query-port", type=int, default=0)
    ap.add_argument("--run-forever", action="store_true")
    ap.add_argument("--trace-path", default="")
    ap.add_argument("--metrics-port", type=int, default=0)
    ap.add_argument("--ini-line", action="append", default=[],
                    help="extra raw config line(s) for the worker ini "
                         "(e.g. 'sloMaxIngestLag = 10')")
    ap.add_argument("--logs", type=int, default=4)
    ap.add_argument("--entries-per-log", type=int, default=256)
    ap.add_argument("--dupes", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--verify", action="store_true",
                    help="also run the serial reference and check parity")
    args = ap.parse_args(argv)
    if args.child:
        os.makedirs(args.state_dir, exist_ok=True)
        rc = child_main(args)
        # Hard exit: every result line is already flushed, and jax's
        # CPU client intermittently segfaults in interpreter teardown
        # (observed -11 AFTER a clean "done" event) — skip atexit so a
        # finished worker can't be scored as crashed.
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(rc)
    out = run_fleet(
        workers=args.workers, n_logs=args.logs,
        entries_per_log=args.entries_per_log, dupes=args.dupes,
        max_batch=args.max_batch, state_dir=args.state_dir,
        checkpoint_period=args.checkpoint_period,
        batch_size=args.batch_size, table_bits=args.table_bits,
        throttle_ms=args.throttle_ms, verify=args.verify)
    print(json.dumps(out, indent=2))
    if args.verify and not out.get("parity"):
        print("PARITY MISMATCH", file=sys.stderr)
        return 1
    if args.verify and not out.get("filter_parity"):
        print("FILTER ARTIFACT MISMATCH", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
