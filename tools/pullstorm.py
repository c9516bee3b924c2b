"""pullstorm: simulated client pull storm against a multi-worker
filter-distribution fleet (ISSUE 13's load proof; ROADMAP item 4).

Builds an epoch sequence of deterministic filter artifacts (synthetic
(issuer, expDate) serial sets with per-epoch churn), publishes every
epoch into W serving workers through the SAME fan-out path ct-fetch
uses in a fleet (``oracle.publish_artifact(..., source="fleet")`` —
byte-identical input on every worker, exactly what the leader's
merged-artifact tick delivers), verifies the workers really serve
byte-identical artifacts (full + every container) over HTTP, then
storms them with N simulated clients:

- **warm** clients (zipf lag 0) hold the latest ETag and issue a
  conditional GET — the steady state, answered ``304`` with zero body
  bytes;
- **lagging** clients (zipf-distributed epoch lag) pull
  ``GET /filter/delta/<theirs>/<latest>``, validate each link against
  the chain manifest, and replay — falling back to a full pull when
  the chain is anchored/evicted away (404);
- **cold** clients full-pull ``GET /filter`` with gzip negotiation
  (a configurable fraction pulls an upstream container instead).

Reports bytes-on-wire against the full-pull counterfactual, the 304
ratio, and latency percentiles. A scaled-down storm runs in tier-1
(tests/test_distrib.py), counted in pulls and bytes.

    python tools/pullstorm.py --clients 10000 --epochs 6 --workers 2
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import queue
import sys
import threading
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def build_epoch_blobs(n_epochs: int, groups: int, per_group: int,
                      churn: int, seed: int) -> list[bytes]:
    """Deterministic epoch sequence: ``groups`` (issuer, expDate)
    sets, ``churn`` groups gaining serials per epoch (the crlite
    shape: most groups untouched epoch to epoch)."""
    from ct_mapreduce_tpu.filter import build_artifact

    rng = np.random.default_rng(seed)
    sets = {
        (f"issuer-{g:03d}", 500_000 + 24 * g): {
            bytes([g % 251, s % 251, 7])
            + bytes([int(x) for x in rng.integers(0, 256, 3)])
            for s in range(per_group)
        }
        for g in range(groups)
    }
    blobs = []
    for e in range(n_epochs):
        if e:
            keys = sorted(sets)
            for i in range(churn):
                key = keys[(e * churn + i) % len(keys)]
                sets[key] = set(sets[key]) | {
                    bytes([e % 251, i % 251])
                    + bytes([int(x) for x in rng.integers(0, 256, 3)])
                    for _ in range(max(1, per_group // 10))}
        blobs.append(build_artifact(sets, fp_rate=0.01,
                                    use_device=False).to_bytes())
    return blobs


def start_fleet(blobs: list[bytes], workers: int,
                max_chain: int) -> list:
    """W serving workers, each fed every epoch through the fleet
    fan-out path. Returns the started QueryServers."""
    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
    from ct_mapreduce_tpu.serve.server import QueryServer

    servers = []
    for _ in range(workers):
        agg = TpuAggregator(capacity=1 << 10, batch_size=64)
        agg.enable_filter_capture()
        srv = QueryServer(agg, 0, filter_first=True,
                          max_delta_chain=max_chain,
                          distrib_history=len(blobs) + 1).start()
        for e, blob in enumerate(blobs):
            srv.oracle.publish_artifact(e, blob, source="fleet")
        servers.append(srv)
    return servers


def verify_fleet_parity(bases: list[str]) -> dict:
    """Every worker serves byte-identical artifacts: full, every
    container, and the manifest's latest hash. Returns the reference
    payload sizes."""
    fulls, etags = [], []
    for base in bases:
        r = urllib.request.urlopen(base + "/filter")
        fulls.append(r.read())
        etags.append(r.headers["ETag"])
    if len({f for f in fulls}) != 1 or len(set(etags)) != 1:
        raise RuntimeError("workers serve DIFFERENT full artifacts")
    sizes = {"full": len(fulls[0]), "etag": etags[0]}
    man = json.loads(urllib.request.urlopen(
        bases[0] + "/filter/manifest").read())
    for kind in man["containers"]:
        payloads = []
        for base in bases:
            payloads.append(urllib.request.urlopen(
                f"{base}/filter/container/{kind}").read())
        if len(set(payloads)) != 1:
            raise RuntimeError(f"workers serve DIFFERENT {kind} "
                               f"containers")
        sizes[kind] = len(payloads[0])
    sizes["manifest"] = man
    return sizes


def run_storm(clients: int = 10_000, epochs: int = 5, groups: int = 40,
              per_group: int = 50, churn: int = 2, workers: int = 2,
              threads: int = 32, max_chain: int = 4,
              cold_fraction: float = 0.05,
              container_fraction: float = 0.2, zipf_a: float = 1.6,
              seed: int = 20260805, validate_every: int = 50,
              force_zstd: bool = False) -> dict:
    """The full storm. Returns the report dict (also printed as JSON
    by the CLI). ``force_zstd`` makes every compressible pull demand
    zstd and fails loudly when the fleet can't serve it (validating
    the zstd wire leg — ROADMAP item 4(c); needs the optional
    ``zstandard`` module on BOTH ends)."""
    from ct_mapreduce_tpu.distrib import (
        ChainManifest,
        apply_chain,
        split_bundle,
    )

    blobs = build_epoch_blobs(epochs, groups, per_group, churn, seed)
    servers = start_fleet(blobs, workers, max_chain)
    bases = [f"http://127.0.0.1:{s.port}" for s in servers]
    try:
        sizes = verify_fleet_parity(bases)
        man = sizes.pop("manifest")
        latest = man["latestEpoch"]
        manifest = ChainManifest.from_json(man)
        latest_etag = sizes["etag"]
        full_size = sizes["full"]

        if force_zstd:
            from ct_mapreduce_tpu.distrib.publish import zstd_available

            if "zstd" not in man["encodings"] or not zstd_available():
                raise RuntimeError(
                    "--force-zstd: the fleet does not advertise zstd "
                    "(install the optional `zstandard` module)")
            import zstandard as _zstd_mod

            accept = "zstd"

            def _decode(body: bytes, encoding) -> bytes:
                if encoding != "zstd":
                    raise RuntimeError(
                        f"--force-zstd: server answered "
                        f"Content-Encoding={encoding!r}, wanted zstd")
                return _zstd_mod.ZstdDecompressor().decompress(body)
        else:
            accept = "gzip"

            def _decode(body: bytes, encoding) -> bytes:
                return (gzip.decompress(body) if encoding == "gzip"
                        else body)

        # Client plan: zipf epoch lag (0 = warm), a cold slice, a
        # container-pulling slice of the colds.
        rng = np.random.default_rng(seed + 1)
        lags = (rng.zipf(zipf_a, size=clients) - 1).clip(0, epochs - 1)
        cold = rng.random(clients) < cold_fraction
        wants_container = rng.random(clients) < container_fraction
        kinds = sorted(k for k in sizes if k not in ("full", "etag"))

        tasks: queue.Queue = queue.Queue()
        for i in range(clients):
            tasks.put(i)
        lock = threading.Lock()
        results = []
        errors = []

        def one_pull(i: int) -> tuple:
            base = bases[i % len(bases)]
            t0 = time.monotonic()
            if cold[i]:
                if wants_container[i] and kinds:
                    kind = kinds[i % len(kinds)]
                    r = urllib.request.urlopen(
                        f"{base}/filter/container/{kind}")
                    return "container", len(r.read()), t0
                req = urllib.request.Request(
                    base + "/filter",
                    headers={"Accept-Encoding": accept})
                r = urllib.request.urlopen(req)
                body = r.read()
                # Client really can use the negotiated encoding.
                _decode(body, r.headers.get("Content-Encoding"))
                return "full", len(body), t0
            lag = int(lags[i])
            if lag == 0:
                req = urllib.request.Request(
                    base + "/filter",
                    headers={"If-None-Match": latest_etag})
                try:
                    r = urllib.request.urlopen(req)
                    return "full", len(r.read()), t0  # ETag rotated
                except urllib.error.HTTPError as err:
                    if err.code != 304:
                        raise
                    err.read()
                    return "304", 0, t0
            try:
                req = urllib.request.Request(
                    f"{base}/filter/delta/{latest - lag}/{latest}",
                    headers={"Accept-Encoding": accept})
                r = urllib.request.urlopen(req)
                wire = r.read()
                bundle = _decode(wire,
                                 r.headers.get("Content-Encoding"))
            except urllib.error.HTTPError as err:
                if err.code != 404:
                    raise
                err.read()
                # Anchored/evicted out: the documented fallback.
                r = urllib.request.urlopen(base + "/filter")
                return "fallback_full", len(r.read()), t0
            if i % validate_every == 0:
                links = split_bundle(bundle)
                manifest.validate_chain(latest - lag, latest, links)
                if apply_chain(blobs[latest - lag], links) \
                        != blobs[latest]:
                    raise RuntimeError(
                        f"delta replay mismatch (lag {lag})")
            return "delta", len(wire), t0

        def worker_loop():
            while True:
                try:
                    i = tasks.get_nowait()
                except queue.Empty:
                    return
                try:
                    kind, n_bytes, t0 = one_pull(i)
                    dt = time.monotonic() - t0
                    with lock:
                        results.append((kind, n_bytes, dt))
                except Exception as err:  # noqa: BLE001 — report, don't hang
                    with lock:
                        errors.append(f"client {i}: "
                                      f"{type(err).__name__}: {err}")

        t_start = time.monotonic()
        pool = [threading.Thread(target=worker_loop, daemon=True)
                for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join()
        wall = time.monotonic() - t_start
        if errors:
            raise RuntimeError(
                f"{len(errors)} client failures, first: {errors[0]}")

        by_kind: dict = {}
        for kind, n_bytes, _ in results:
            cnt, tot = by_kind.get(kind, (0, 0))
            by_kind[kind] = (cnt + 1, tot + n_bytes)
        lat = np.array(sorted(dt for _, _, dt in results))
        bytes_on_wire = sum(tot for _, tot in by_kind.values())
        n304 = by_kind.get("304", (0, 0))[0]
        n_delta, delta_bytes = by_kind.get("delta", (0, 0))
        counterfactual = len(results) * full_size
        d3_clients = n304 + n_delta
        d3_bytes = delta_bytes  # 304s add zero body bytes
        report = {
            "clients": len(results),
            "workers": workers,
            "epochs": epochs,
            "full_artifact_bytes": full_size,
            "pulls": {k: {"count": c, "bytes": b}
                      for k, (c, b) in sorted(by_kind.items())},
            "ratio_304": round(n304 / max(1, len(results)), 4),
            "bytes_on_wire": bytes_on_wire,
            "counterfactual_full_bytes": counterfactual,
            "wire_vs_counterfactual": round(
                bytes_on_wire / max(1, counterfactual), 4),
            "delta_304_clients": d3_clients,
            "delta_304_bytes": d3_bytes,
            "delta_304_counterfactual": d3_clients * full_size,
            "delta_304_vs_full": round(
                d3_bytes / max(1, d3_clients * full_size), 4),
            "p50_ms": round(float(lat[len(lat) // 2]) * 1e3, 3),
            "p99_ms": round(float(lat[int(len(lat) * 0.99)]) * 1e3, 3),
            "wall_s": round(wall, 3),
            "pulls_per_s": round(len(results) / max(wall, 1e-9), 1),
            "worker_parity": 1,
            "zstd_available": "zstd" in man["encodings"],
        }
        return report
    finally:
        for s in servers:
            s.stop()


# -- the live-fleet leg (ROADMAP 4(a)) -----------------------------------


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _http_json(url: str, timeout: float = 5.0) -> dict:
    return json.loads(urllib.request.urlopen(url, timeout=timeout).read())


def run_live_fleet_storm(clients: int = 900, threads: int = 12,
                         n_logs: int = 4, entries_per_log: int = 640,
                         throttle_ms: float = 500.0,
                         state_dir: str = "") -> dict:
    """ROADMAP 4(a): the storm driven against a LIVE ``tools/fleet.py``
    fleet instead of the direct fan-out path. Two real ct-fetch worker
    processes ingest a throttled fixture under a 500 ms checkpoint
    cadence, each serving ``/filter`` + ``/filter/delta`` from its own
    queryPort while the leader's merged CTMRFL02 artifact fans out
    every epoch tick. Mid-storm the LEADER is SIGKILLed; the parent
    expires its election lease (the 5-minute production TTL compressed
    to harness timescale — exactly the expiry ``maybe_promote``
    inherits from), the surviving follower promotes itself and keeps
    publishing epochs, and the dead worker is respawned and warm-
    rejoins. The leg then proves delta-chain continuity end to end:
    every consecutive captured epoch pair AND one span straddling the
    failover replay byte-identical via the survivor's chain, the
    final artifact is byte-identical on both workers, and an offline
    merge of the worker checkpoints reproduces the served bytes."""
    import hashlib
    import http.client
    import signal
    import subprocess
    import tempfile
    from datetime import datetime, timezone

    from tools import fleet as harness

    from ct_mapreduce_tpu.distrib import (
        ChainManifest,
        apply_chain,
        split_bundle,
    )
    from ct_mapreduce_tpu.storage.rediscache import RedisCache
    from ct_mapreduce_tpu.utils.miniredis import MiniRedis

    state_dir = state_dir or tempfile.mkdtemp(prefix="ct-livestorm-")
    os.makedirs(state_dir, exist_ok=True)
    fixture_path = os.path.join(state_dir, "fixture.json")
    # Small batches + a heavy per-batch throttle stretch the ingest
    # window far past worker startup, so the leader SIGKILL lands
    # MID-INGEST and the promoted follower still has real churn to
    # publish (post-failover epochs require changing bytes).
    fixture = harness.build_fixture(
        fixture_path, n_logs=n_logs, entries_per_log=entries_per_log,
        dupes=16, max_batch=16)
    total_entries = sum(len(v) for v in fixture["logs"].values())
    ports = [_free_port(), _free_port()]
    bases = [f"http://127.0.0.1:{p}" for p in ports]

    redis = MiniRedis().start()
    cache = RedisCache(redis.address)
    procs: list = []  # (worker_id, Popen)
    captured: list[dict] = []  # {epoch, blob, etag, t}
    cap_lock = threading.Lock()
    cap_stop = threading.Event()
    t0 = time.monotonic()

    def spawn(worker_id: int):
        # No persistent compile cache for any process in a
        # kill-and-resume sequence (tools/fleet.py::spawn_worker).
        p = harness.spawn_worker(
            worker_id, 2, fixture_path, state_dir, redis.address,
            checkpoint_period="1s", throttle_ms=throttle_ms,
            compile_cache=False, query_port=ports[worker_id],
            run_forever=True)
        procs.append((worker_id, p))
        return p

    def capture_loop():
        """Tail the SURVIVOR's (/w1's) distribution store: one entry
        per store epoch, blob pinned to the manifest's latestSha256
        (re-polls when a publish races the full-artifact GET)."""
        while not cap_stop.is_set():
            try:
                man = _http_json(bases[1] + "/filter/manifest")
                latest = man.get("latestEpoch", -1)
                with cap_lock:
                    have = captured[-1]["epoch"] if captured else -1
                if latest > have:
                    r = urllib.request.urlopen(bases[1] + "/filter",
                                               timeout=5)
                    blob = r.read()
                    if hashlib.sha256(blob).hexdigest() \
                            == man["latestSha256"]:
                        with cap_lock:
                            if not captured \
                                    or captured[-1]["epoch"] < latest:
                                captured.append({
                                    "epoch": latest, "blob": blob,
                                    "etag": r.headers["ETag"],
                                    "t": time.monotonic()})
            except Exception:
                pass  # worker mid-start / mid-restart: retry
            cap_stop.wait(0.2)

    def wait_for(cond, what: str, deadline_s: float):
        deadline = time.monotonic() + deadline_s
        while time.monotonic() < deadline:
            if cond():
                return
            for wid, p in procs:
                if p.returncode is None and p.poll() is not None \
                        and p.returncode != -signal.SIGKILL:
                    out = p.stdout.read() if p.stdout else ""
                    raise RuntimeError(
                        f"worker {wid} died rc={p.returncode} while "
                        f"waiting for {what}:\n{out[-4000:]}")
            time.sleep(0.25)
        raise RuntimeError(f"timed out waiting for {what}")

    def storm_phase(n: int, label: str) -> dict:
        """n mixed clients against both workers; a connection-refused
        replica (the killed leader, or the respawn window) retries on
        the peer and is counted as a failover retry."""
        with cap_lock:
            snap = list(captured)
        latest = snap[-1]
        rng = np.random.default_rng(20260807 + n)
        lags = rng.integers(0, max(1, len(snap)), size=n)
        cold = rng.random(n) < 0.1
        lock = threading.Lock()
        results, errors = [], []
        retries = [0]
        tasks: queue.Queue = queue.Queue()
        for i in range(n):
            tasks.put(i)

        def one_pull(i: int) -> tuple:
            t_req = time.monotonic()
            attempt = 0
            base = bases[i % len(bases)]
            while True:
                try:
                    if cold[i]:
                        r = urllib.request.urlopen(base + "/filter",
                                                   timeout=10)
                        return "full", len(r.read()), t_req
                    lag = int(lags[i])
                    if lag == 0:
                        req = urllib.request.Request(
                            base + "/filter",
                            headers={"If-None-Match": latest["etag"]})
                        try:
                            r = urllib.request.urlopen(req, timeout=10)
                            return "full", len(r.read()), t_req
                        except urllib.error.HTTPError as err:
                            if err.code != 304:
                                raise
                            err.read()
                            return "304", 0, t_req
                    mine = snap[len(snap) - 1 - lag]
                    try:
                        r = urllib.request.urlopen(
                            f"{base}/filter/delta/{mine['epoch']}"
                            f"/{latest['epoch']}", timeout=10)
                        wire = r.read()
                    except urllib.error.HTTPError as err:
                        if err.code != 404:
                            raise
                        err.read()
                        # Evicted/anchored away (e.g. the respawned
                        # worker's fresh store): documented fallback.
                        r = urllib.request.urlopen(base + "/filter",
                                                   timeout=10)
                        return "fallback_full", len(r.read()), t_req
                    if apply_chain(mine["blob"], split_bundle(wire)) \
                            != latest["blob"]:
                        raise RuntimeError(
                            f"delta replay mismatch (lag {lag})")
                    return "delta", len(wire), t_req
                except (urllib.error.URLError, ConnectionError,
                        TimeoutError, http.client.HTTPException):
                    attempt += 1
                    if attempt >= 4:
                        raise
                    with lock:
                        retries[0] += 1
                    base = bases[(i + attempt) % len(bases)]
                    time.sleep(0.2)

        def worker_loop():
            while True:
                try:
                    i = tasks.get_nowait()
                except queue.Empty:
                    return
                try:
                    kind, n_bytes, t_req = one_pull(i)
                    with lock:
                        results.append(
                            (kind, n_bytes, time.monotonic() - t_req))
                except Exception as err:  # noqa: BLE001
                    with lock:
                        errors.append(f"client {i}: "
                                      f"{type(err).__name__}: {err}")

        pool = [threading.Thread(target=worker_loop, daemon=True)
                for _ in range(threads)]
        t_start = time.monotonic()
        for t in pool:
            t.start()
        for t in pool:
            t.join()
        if errors:
            raise RuntimeError(f"{label}: {len(errors)} client "
                               f"failures, first: {errors[0]}")
        by_kind: dict = {}
        for kind, n_bytes, _ in results:
            cnt, tot = by_kind.get(kind, (0, 0))
            by_kind[kind] = (cnt + 1, tot + n_bytes)
        lat = sorted(dt for _, _, dt in results)
        return {
            "clients": len(results),
            "pulls": {k: {"count": c, "bytes": b}
                      for k, (c, b) in sorted(by_kind.items())},
            "failover_retries": retries[0],
            "p50_ms": round(lat[len(lat) // 2] * 1e3, 3),
            "p99_ms": round(lat[int(len(lat) * 0.99)] * 1e3, 3),
            "wall_s": round(time.monotonic() - t_start, 3),
        }

    try:
        # Leader first, alone, so the kill target is deterministic.
        spawn(0)
        wait_for(lambda: cache.get("leader-ct-fetch") is not None,
                 "leader election", 300)
        spawn(1)
        cap_thread = threading.Thread(target=capture_loop, daemon=True)
        cap_thread.start()
        wait_for(lambda: len(captured) >= 2,
                 "two published fleet epochs", 300)

        phase1 = storm_phase(clients // 3, "pre-failover")

        # Mid-storm failover: kill the leader 1 s into phase 2, then
        # expire its election lease so the follower's maybe_promote
        # can win now rather than at the 5-minute production TTL.
        phase2_out: dict = {}

        def phase2_run():
            phase2_out.update(storm_phase(clients // 3, "mid-failover"))

        p2 = threading.Thread(target=phase2_run)
        p2.start()
        time.sleep(1.0)
        victim = next(p for wid, p in procs if wid == 0)
        os.kill(victim.pid, signal.SIGKILL)
        t_kill = time.monotonic()
        cache.expire_at("leader-ct-fetch",
                        datetime(1970, 1, 2, tzinfo=timezone.utc))
        kill_cursors = harness.read_cursors(redis.address, fixture, 2)
        ingest_frac_at_kill = round(
            sum(max(0, v + 1) for v in kill_cursors.values())
            / max(1, total_entries), 3)
        p2.join()
        victim.wait(timeout=30)
        victim_out = victim.stdout.read() if victim.stdout else ""
        victim.stdout.close()

        def post_kill_epochs():
            with cap_lock:
                return [c for c in captured if c["t"] > t_kill]

        wait_for(lambda: len(post_kill_epochs()) >= 1,
                 "a post-failover epoch from the promoted follower",
                 180)
        failover_s = post_kill_epochs()[0]["t"] - t_kill

        # Respawn the dead leader: warm rejoin as a follower.
        respawn = spawn(0)
        wait_for(lambda: _can_reach(bases[0]), "leader respawn", 300)
        phase3 = storm_phase(clients - 2 * (clients // 3),
                             "post-respawn")

        # Quiescence: every log cursor at tree size, then the captured
        # chain stable (the final merged artifact covers the corpus).
        def ingest_done():
            cur = harness.read_cursors(redis.address, fixture, 2)
            per_log = {}
            for key, pos in cur.items():
                root = key.split("#")[0]
                per_log[root] = max(per_log.get(root, 0), pos)
            return len(per_log) == n_logs and all(
                pos >= entries_per_log - 1 for pos in per_log.values())

        wait_for(ingest_done, "ingest completion", 600)

        def chain_stable():
            with cap_lock:
                return captured and time.monotonic() - captured[-1]["t"] > 6.0

        wait_for(chain_stable, "chain quiescence", 120)

        # -- continuity + parity verdicts --------------------------------
        with cap_lock:
            snap = list(captured)
        pre = [c for c in snap if c["t"] <= t_kill]
        post = [c for c in snap if c["t"] > t_kill]
        if not pre or not post:
            raise RuntimeError(
                f"failover not straddled: {len(pre)} pre-kill epochs, "
                f"{len(post)} post-kill")
        man = _http_json(bases[1] + "/filter/manifest")
        manifest = ChainManifest.from_json(man)
        pairs_replayed, pairs_404 = 0, 0
        for a, b in zip(snap, snap[1:]):
            try:
                wire = urllib.request.urlopen(
                    f"{bases[1]}/filter/delta/{a['epoch']}"
                    f"/{b['epoch']}", timeout=10).read()
            except urllib.error.HTTPError as err:
                if err.code != 404:
                    raise
                err.read()
                pairs_404 += 1  # evicted/anchored away: fallback path
                continue
            links = split_bundle(wire)
            manifest.validate_chain(a["epoch"], b["epoch"], links)
            if apply_chain(a["blob"], links) != b["blob"]:
                raise RuntimeError(
                    f"chain replay {a['epoch']}→{b['epoch']} diverged")
            pairs_replayed += 1
        if not pairs_replayed:
            raise RuntimeError("no consecutive epoch pair replayed")
        # The leg's reason to exist: one chain span straddling the
        # leader failover must replay byte-identically.
        boundary = pre[-1]
        wire = urllib.request.urlopen(
            f"{bases[1]}/filter/delta/{boundary['epoch']}"
            f"/{snap[-1]['epoch']}", timeout=10).read()
        links = split_bundle(wire)
        manifest.validate_chain(boundary["epoch"], snap[-1]["epoch"],
                                links)
        if apply_chain(boundary["blob"], links) != snap[-1]["blob"]:
            raise RuntimeError("failover-straddling chain diverged")

        finals, final_etags = [], []
        for base in bases:
            r = urllib.request.urlopen(base + "/filter", timeout=10)
            finals.append(r.read())
            final_etags.append(r.headers["ETag"])
        if len(set(finals)) != 1 or len(set(final_etags)) != 1:
            raise RuntimeError("workers serve DIFFERENT final "
                               "artifacts after failover")

        # Shutdown broadcast, then the offline determinism cross-check:
        # merging the workers' final checkpoints must reproduce the
        # bytes the fleet served.
        cache.put("fleet-stop-ct-fetch", "storm complete")
        outs = {}
        for wid, p in procs:
            if p is victim:
                continue
            out, _ = p.communicate(timeout=180)
            outs[wid] = out
            if p.returncode != 0:
                raise RuntimeError(
                    f"worker {wid} exited rc={p.returncode}:\n"
                    f"{out[-4000:]}")
        state_paths = [os.path.join(state_dir, f"agg.w{w}.npz")
                       for w in range(2)]
        offline = harness.filter_bytes(state_paths)
        if offline != finals[0]:
            raise RuntimeError(
                "offline checkpoint merge does not reproduce the "
                f"served artifact ({len(offline)} vs "
                f"{len(finals[0])} bytes)")

        if "(leader" not in victim_out:
            raise RuntimeError(
                "kill target was not the leader — leg invalid:\n"
                + victim_out[-2000:])
        respawn_events = harness.child_events(outs[0])
        resume = next(e for e in respawn_events
                      if e["event"] == "start")["resume_cursors"]
        if not resume or not any(v > 0 for v in resume.values()):
            raise RuntimeError(
                f"respawned leader did not warm-resume: {resume}")

        return {
            "metric": "ct_filter_live_fleet_storm",
            "workers": 2,
            "logs": n_logs,
            "entries": total_entries,
            "format": finals[0][:8].decode(),
            "full_artifact_bytes": len(finals[0]),
            "epochs_captured": len(snap),
            "epochs_pre_kill": len(pre),
            "epochs_post_kill": len(post),
            "failover_s": round(failover_s, 3),
            "ingest_frac_at_kill": ingest_frac_at_kill,
            "chain_pairs_replayed": pairs_replayed,
            "chain_pairs_404": pairs_404,
            "chain_spans_failover": 1,
            "worker_parity": 1,
            "offline_merge_parity": 1,
            "leader_warm_resume": 1,
            "storm": {"pre_failover": phase1, "mid_failover": phase2_out,
                      "post_respawn": phase3},
            "wall_s": round(time.monotonic() - t0, 3),
        }
    finally:
        cap_stop.set()
        for _, p in procs:
            if p.poll() is None:
                p.kill()
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    pass
        cache.close()
        redis.stop()


def _can_reach(base: str) -> bool:
    try:
        urllib.request.urlopen(base + "/filter", timeout=2).read()
        return True
    except Exception:
        return False


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="pullstorm")
    p.add_argument("--clients", type=int, default=10_000)
    p.add_argument("--epochs", type=int, default=5)
    p.add_argument("--groups", type=int, default=40)
    p.add_argument("--per-group", type=int, default=50)
    p.add_argument("--churn", type=int, default=2)
    p.add_argument("--workers", type=int, default=2)
    p.add_argument("--threads", type=int, default=32)
    p.add_argument("--max-chain", type=int, default=4)
    p.add_argument("--cold", type=float, default=0.05)
    p.add_argument("--containers", type=float, default=0.2)
    p.add_argument("--zipf", type=float, default=1.6)
    p.add_argument("--seed", type=int, default=20260805)
    p.add_argument("--force-zstd", action="store_true",
                   help="every compressible pull demands zstd; fails "
                        "when the optional zstandard module is absent "
                        "(validates the zstd wire leg)")
    p.add_argument("--live-fleet", action="store_true",
                   help="drive the storm against a LIVE tools/fleet.py "
                        "run with a leader SIGKILL + lease-expiry "
                        "failover mid-storm (ROADMAP 4(a))")
    args = p.parse_args(argv)
    if args.live_fleet:
        report = run_live_fleet_storm(clients=args.clients,
                                      threads=args.threads)
        print(json.dumps(report, indent=2))
        return 0
    report = run_storm(
        clients=args.clients, epochs=args.epochs, groups=args.groups,
        per_group=args.per_group, churn=args.churn,
        workers=args.workers, threads=args.threads,
        max_chain=args.max_chain, cold_fraction=args.cold,
        container_fraction=args.containers, zipf_a=args.zipf,
        seed=args.seed, force_zstd=args.force_zstd)
    print(json.dumps(report, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
