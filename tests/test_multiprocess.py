"""True multi-process scale-out.

Two lanes:

1. **Simulated ingest fleet (tier-1, CPU-complete):** W=2 real
   ``ct-fetch`` worker PROCESSES coordinated through miniredis — SETNX
   election, start barrier, heartbeats, leader-published checkpoint
   epochs — over disjoint rendezvous partitions of a shared fakelog
   fixture (tools/fleet.py harness), with the merged per-worker
   aggregates byte-identical to a single-worker run of the same
   entries; plus the SIGKILL-and-resume warm-restart contract. No XLA
   multiprocess collectives required, so these gates run (not skip) on
   the CPU CI backend.

2. **Global-mesh collectives:** the explicit-arguments path of
   ``initialize_multihost`` (``jax.distributed.initialize``) with one
   mesh-global ShardedDedup step whose row-sharded table spans both
   processes' devices. Still capability-gated: this jax build's CPU
   backend cannot run cross-process collectives (the fleet lane above
   is the one that must always run).
"""

import json
import os
import signal
import socket
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import pytest

from tests.conftest import compile_cache_env

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

_CHILD = textwrap.dedent("""
    import os, sys

    port, pid, nprocs = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    os.environ.pop("CT_TPU_TESTS", None)

    from ct_mapreduce_tpu.parallel.distributed import (
        DistributedCoordinator,
        initialize_multihost,
        is_leader,
    )

    initialize_multihost(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=nprocs,
        process_id=pid,
    )

    import jax
    import numpy as np

    assert jax.process_index() == pid, (jax.process_index(), pid)
    assert jax.process_count() == nprocs
    assert len(jax.devices()) == 2 * nprocs  # global device view
    assert is_leader() == (pid == 0)

    coord = DistributedCoordinator("mp-test")
    if coord.await_leader():
        print(f"proc{pid}: leader", flush=True)
        coord.send_start()
    else:
        print(f"proc{pid}: follower", flush=True)
        coord.await_start(timeout_s=120)
    print(f"proc{pid}: barrier released", flush=True)

    # One global-mesh sharded dedup step: the table's rows are sharded
    # over all 4 devices across BOTH processes; key routing rides
    # all_to_all, per-issuer counts come back psum'd (replicated, so
    # every process can read them).
    from jax.sharding import Mesh

    from ct_mapreduce_tpu.agg import sharded

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "graft_entry", os.environ["CT_GRAFT_ENTRY"])
    ge = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ge)

    mesh = Mesh(np.asarray(jax.devices()), (sharded.AXIS,))
    n = mesh.devices.size
    batch = 16 * n
    data, length, issuer_idx, valid, _ = ge._packed_batch(
        batch, 1024, n_issuers=2)
    # Each process generated its own signing keys — broadcast proc 0's
    # batch so every controller feeds identical global values (the
    # same-value contract of multi-process device_put), riding the
    # distributed runtime's own collective.
    from jax.experimental import multihost_utils

    data, length, issuer_idx, valid = (
        np.asarray(multihost_utils.broadcast_one_to_all(x))
        for x in (data, length, issuer_idx, valid)
    )

    dedup = sharded.ShardedDedup(mesh, capacity=1024 * n)
    out = dedup.step(data, length, issuer_idx, valid,
                     now_hour=ge._NOW_HOUR)
    counts = np.asarray(out.issuer_unknown_counts)  # replicated → readable
    total = dedup.total_count()
    host_lane_ct = int(np.asarray(
        jax.jit(lambda x: x.sum())(out.host_lane)))
    assert total + host_lane_ct == batch, (total, host_lane_ct, batch)
    assert int(counts.sum()) == total, (int(counts.sum()), total)

    out2 = dedup.step(data, length, issuer_idx, valid,
                      now_hour=ge._NOW_HOUR)
    assert dedup.total_count() == total  # replay inserted nothing
    print(f"proc{pid}: sharded step OK total={total}", flush=True)

    # Auto-growth must be forced OFF under multi-host: its trigger is
    # per-process and would fire out of lockstep (collective deadlock).
    from ct_mapreduce_tpu.agg.sharded_agg import ShardedAggregator

    agg = ShardedAggregator(mesh, capacity=1024 * n, batch_size=batch,
                            grow_at=0.7)
    assert agg.grow_at == 0, agg.grow_at
    print(f"proc{pid}: multi-host growth guard OK", flush=True)
""")


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# -- the simulated ingest fleet (tier-1, no collectives needed) ---------


@pytest.fixture()
def compile_cache(tmp_path_factory, monkeypatch):
    """One persistent XLA compile cache shared by every worker
    subprocess in this module: the W children compile identical tiny
    CPU programs, so only the first pays (spawn_worker forwards the
    env)."""
    path = str(tmp_path_factory.getbasetemp().parent / "fleet-xla-cache")
    for name, value in compile_cache_env(path).items():
        monkeypatch.setenv(name, value)
    return path


@pytest.mark.timeout(340)
def test_fleet_two_worker_parity(tmp_path, compile_cache):
    """ISSUE 9 acceptance #1: two ct-fetch worker processes over
    miniredis and disjoint fakelog partitions produce a merged
    aggregate byte-identical (serial counts per (issuer, expDate),
    issuer CRL/DN metadata, verify counts) to a single-worker run of
    the same entries."""
    from tools import fleet as harness

    from ct_mapreduce_tpu.ingest import ctclient
    from ct_mapreduce_tpu.utils.miniredis import MiniRedis

    fixture_path = str(tmp_path / "fixture.json")
    fixture = harness.build_fixture(
        fixture_path, n_logs=3, entries_per_log=64, dupes=8, max_batch=64)
    total = sum(len(v) for v in fixture["logs"].values())

    server = MiniRedis().start()
    try:
        procs = [
            harness.spawn_worker(
                w, 2, fixture_path, str(tmp_path / f"w{w}"),
                server.address, checkpoint_period="500ms")
            for w in range(2)
        ]
        outs = [p.communicate(timeout=300)[0] for p in procs]
    finally:
        server.stop()
    for w, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {w} failed:\n{out[-4000:]}"
    events = [harness.child_events(out) for out in outs]
    dones = [next(e for e in evs if e["event"] == "done")
             for evs in events]

    # The partition really was disjoint and covering, and both workers
    # had work (3 fixture logs split 1/2 under the rendezvous hash).
    owned = {d["worker"]: d["owned_logs"] for d in dones}
    flat = [u for logs in owned.values() for u in logs]
    assert sorted(flat) == sorted(fixture["logs"])
    assert all(len(logs) >= 1 for logs in owned.values()), owned

    # Merged aggregate == the single-worker truth, byte-identical.
    merged = harness.merged_snapshot([d["state_path"] for d in dones])
    ref = harness.run_serial_reference(fixture, str(tmp_path))
    assert merged == ref
    assert 0 < merged["total"] <= total

    # Round-15 artifact determinism (the tools/fleet.py --verify
    # contract): the merged fleet FILTER compiled from the two worker
    # checkpoints is byte-identical to the serial run's — worker-local
    # issuer indices cancel out of the canonical keys.
    fleet_blob = harness.filter_bytes([d["state_path"] for d in dones])
    serial_blob = harness.filter_bytes([str(tmp_path / "serial.npz")])
    assert fleet_blob == serial_blob
    assert len(fleet_blob) > 12  # a real artifact, not an empty header


@pytest.mark.timeout(340)
def test_fleet_kill_and_resume(tmp_path, compile_cache):
    """ISSUE 9 acceptance #2: a worker SIGKILLed mid-ingest after >=1
    checkpoint resumes from its checkpoint cursor — NOT entry 0 — and
    the final aggregate equals the uninterrupted run's."""
    from tools import fleet as harness

    from ct_mapreduce_tpu.ingest.ctclient import short_url
    from ct_mapreduce_tpu.storage.rediscache import RedisCache
    from ct_mapreduce_tpu.utils.miniredis import MiniRedis

    fixture_path = str(tmp_path / "fixture.json")
    fixture = harness.build_fixture(
        fixture_path, n_logs=1, entries_per_log=224, dupes=16,
        max_batch=32)
    url = next(iter(fixture["logs"]))
    total = len(fixture["logs"][url])
    wdir = str(tmp_path / "w0")

    server = MiniRedis().start()
    try:
        # Victim run: throttled downloads + a 300 ms checkpoint cadence
        # guarantee >=1 durable (cursor, aggregate) checkpoint lands
        # mid-ingest; then SIGKILL — no graceful shutdown path runs.
        # Cache policy (see tools/fleet.py::spawn_worker, round 14): the victim consumes the suite's warm cache
        # READ-ONLY (a kill can then never leave a truncated entry),
        # and the RESUMED process runs with NO persistent cache at all
        # — with one, this box's jax build intermittently corrupts the
        # resumed process's native heap (XLA CHECK aborts, glibc
        # aborts, or silently garbage table rows in its final
        # checkpoint — ~1 in 3 runs). The contract under test is the
        # CHECKPOINT's, not the compile cache's.
        victim = harness.spawn_worker(
            0, 1, fixture_path, wdir, server.address,
            checkpoint_period="300ms", throttle_ms=150,
            coordinator="redis", compile_cache_readonly=True)
        cache = RedisCache(server.address)
        npz = os.path.join(wdir, "agg.npz")
        kill_cursor = 0
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            state = cache.load_log_state(short_url(url))
            cursor = state.max_entry if state else 0
            if os.path.exists(npz) and 0 < cursor < total:
                kill_cursor = cursor
                break
            assert victim.poll() is None, (
                "worker finished before a mid-ingest checkpoint:\n"
                + victim.communicate()[0][-4000:])
            time.sleep(0.05)
        assert 0 < kill_cursor < total, "no mid-ingest checkpoint seen"
        os.kill(victim.pid, signal.SIGKILL)
        victim.wait(timeout=30)
        victim.stdout.close()

        # The durable contract at the moment of death: the atomically-
        # written checkpoint must be a VALID readable aggregate (the
        # temp-file + rename discipline means a kill can never leave a
        # torn snapshot behind).
        victim_snap = harness.merged_snapshot([npz])
        assert 0 < victim_snap["total"] <= total

        # Restart the same worker id — IN-PROCESS (this interpreter is
        # the restarted worker; kernels are warm, and no fresh
        # cache-consuming child exists for the environment bug noted
        # above to bite): it must resume from the durable checkpoint
        # cursor (the post-checkpoint tail re-folds idempotently
        # through the dedup table) and run to completion. The
        # full-subprocess restart stays drivable via tools/fleet.py.
        from ct_mapreduce_tpu.cmd import ct_fetch
        from ct_mapreduce_tpu.ingest import ctclient

        resume_state = cache.load_log_state(short_url(url))
        resume_cursor = resume_state.max_entry if resume_state else 0
        transport = harness.FixtureTransport(fixture)
        orig_transport = ctclient._default_transport
        ctclient._default_transport = transport
        try:
            ini = os.path.join(wdir, "resume.ini")
            harness.write_worker_ini(
                ini, fixture, npz, redis_addr=server.address,
                checkpoint_period="300ms", coordinator="redis")
            rc = ct_fetch.main(["-config", ini, "-nobars"])
        finally:
            ctclient._default_transport = orig_transport
        cache.close()
    finally:
        server.stop()
    assert rc == 0
    # The span evidence: the restarted worker's durable cursor equals
    # the checkpoint position (>= where the kill was observed, > 0),
    # and its FIRST get-entries fetch started there — no replay from
    # entry 0.
    assert resume_cursor >= kill_cursor > 0, (resume_cursor, kill_cursor)
    assert transport.entry_requests, "restart fetched nothing"
    assert min(transport.entry_requests) == resume_cursor

    merged = harness.merged_snapshot([npz])
    ref = harness.run_serial_reference(fixture, str(tmp_path))
    assert merged == ref
    assert merged["total"] > 0


@pytest.mark.timeout(240)
def test_fleet_healthz_section_served_live(tmp_path):
    """A one-worker fleet over the redis coordinator, run in this
    process with ``metricsPort`` set: while it ingests, ``/healthz``
    carries the ``fleet`` section (role, membership, the partition map,
    the leader's checkpoint epoch), and the aggregate it leaves equals
    the serial run's. The downloads are throttled so that the run
    outlasts several 150 ms epochs and many polls."""
    import threading
    import urllib.request

    from tools import fleet as harness

    from ct_mapreduce_tpu.agg.aggregator import HostSnapshotAggregator
    from ct_mapreduce_tpu.cmd import ct_fetch
    from ct_mapreduce_tpu.ingest import ctclient
    from ct_mapreduce_tpu.utils.miniredis import MiniRedis

    fixture = harness.build_fixture(
        str(tmp_path / "fixture.json"), n_logs=2, entries_per_log=64,
        dupes=6, max_batch=64)
    urls = list(fixture["logs"])
    port = _free_port()
    ini = str(tmp_path / "worker.ini")
    state = str(tmp_path / "agg.npz")
    bodies = []
    stop = threading.Event()

    def poll():
        while not stop.is_set():
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/healthz",
                        timeout=1) as resp:
                    body = json.loads(resp.read())
                if "fleet" in body:
                    bodies.append(body["fleet"])
            except (OSError, ValueError):
                pass
            time.sleep(0.05)

    server = MiniRedis().start()
    orig_transport = ctclient._default_transport
    paced = harness.FixtureTransport(fixture, throttle_ms=150)
    paced.max_batch = 16
    ctclient._default_transport = paced
    poller = threading.Thread(target=poll, daemon=True)
    try:
        harness.write_worker_ini(
            ini, fixture, state, redis_addr=server.address,
            checkpoint_period="150ms", coordinator="redis",
            metrics_port=port)
        poller.start()
        rc = ct_fetch.main(["-config", ini, "-nobars"])
    finally:
        stop.set()
        poller.join(5)
        ctclient._default_transport = orig_transport
        server.stop()
    assert rc == 0
    assert bodies, "no /healthz body carried the fleet section"
    assert bodies[-1]["role"] == "leader"
    assert bodies[-1]["workers_alive"] == [0]
    part = next((b["partition"] for b in bodies if b["partition"]), None)
    assert part == {u: 0 for u in urls}
    assert max(b.get("checkpoint_epoch", 0) for b in bodies) >= 1

    agg = HostSnapshotAggregator(capacity=1 << 10)
    agg.load_checkpoint(state)
    assert harness.snapshot_jsonable(agg.drain()) == \
        harness.run_serial_reference(fixture, str(tmp_path))


@pytest.mark.timeout(420)
def test_fleet_observability_plane_live(tmp_path, compile_cache):
    """The fleet-wide observability plane over two live worker
    processes (tests/test_fleetobs.py holds the same pieces to their
    contracts in one process): the rollup on ``/healthz/fleet`` sees
    both workers and a leader; once ingest is quiet the fleet's insert
    counter on ``/metrics/fleet`` is the sum of the two workers' own
    ``/metrics`` and every fleet counter in that body the sum of its
    ``{worker=...}`` lines; a stopped worker turns the rollup 503 with
    a reason that names it, and the rollup recovers when it continues;
    and a query's ``trace_id``, minted in this process, is on a span of
    the worker that served it once the three exports are merged into
    one timeline. No time is asserted."""
    import re
    import urllib.error
    import urllib.request

    from tools import fleet as harness

    from ct_mapreduce_tpu.ingest.fleet import partition_map
    from ct_mapreduce_tpu.serve.client import QueryClient
    from ct_mapreduce_tpu.telemetry import fleetobs, trace
    from ct_mapreduce_tpu.utils.miniredis import MiniRedis

    fixture_path = str(tmp_path / "fixture.json")
    fixture = harness.build_fixture(
        fixture_path, n_logs=2, entries_per_log=48, dupes=4, max_batch=32)
    urls = list(fixture["logs"])
    assert set(partition_map(urls, 2).values()) == {0, 1}

    def http_get(url):
        try:
            with urllib.request.urlopen(url, timeout=3.0) as resp:
                return resp.getcode(), resp.read().decode()
        except urllib.error.HTTPError as err:
            return err.code, err.read().decode()
        except OSError:
            return -1, ""

    def counter_of(body, name):
        m = re.search(rf"(?m)^{re.escape(name)} ([0-9eE.+-]+)$", body)
        return float(m.group(1)) if m else -1.0

    def wait_for(what, probe, seconds):
        deadline = time.monotonic() + seconds
        while time.monotonic() < deadline:
            for w, p in enumerate(procs):
                assert p.poll() is None, (
                    f"worker {w} died: {p.communicate()[0][-1500:]}")
            got = probe()
            if got is not None:
                return got
            time.sleep(0.25)
        raise AssertionError(f"timed out waiting for {what}")

    mports = [_free_port(), _free_port()]
    qport = _free_port()
    trace_paths = [str(tmp_path / f"w{w}-trace.json") for w in range(2)]
    fleet_url = f"http://127.0.0.1:{mports[0]}/healthz/fleet"
    insert_key = "ct_fetch_insertCertificate"
    n_queries = 4

    server = MiniRedis().start()
    procs: list = []
    try:
        procs = [
            harness.spawn_worker(
                w, 2, fixture_path, str(tmp_path / f"obs-w{w}"),
                server.address, checkpoint_period="500ms",
                coordinator="redis", run_forever=True,
                query_port=(qport if w == 0 else 0),
                trace_path=trace_paths[w], metrics_port=mports[w],
                # Thresholds far away: the SLO rules run and publish
                # their gauges without a breach.
                ini_lines=("sloMaxIngestLag = 1000000",
                           "sloMaxServeP99Ms = 60000"),
                # Heartbeats every 2 s; 4 s leaves one missed beat.
                extra_env={"CTMR_FLEET_LIVENESS_S": "4.0"})
            for w in range(2)
        ]

        def healthy_rollup():
            st, raw = http_get(fleet_url)
            body = json.loads(raw) if st == 200 else {}
            if body.get("healthy") and body.get("workers_reporting") == 2:
                return body
            return None

        rollup = wait_for("a healthy rollup of two", healthy_rollup, 300)
        assert not rollup["missing"] and rollup["leader_epoch_skew"] <= 1
        assert "leader" in [e["role"] for e in rollup["workers"].values()]

        def quiet_parity():
            live = [counter_of(
                http_get(f"http://127.0.0.1:{p}/metrics")[1], insert_key)
                for p in mports]
            st, body = http_get(f"http://127.0.0.1:{mports[0]}/metrics/fleet")
            if (st == 200 and min(live) > 0
                    and counter_of(body, insert_key) == sum(live)):
                return body
            return None

        mf_body = wait_for("fleet and live insert counters to agree",
                           quiet_parity, 180)
        assert fleetobs.fleet_counter_parity(mf_body) == []
        for w in range(2):
            assert f'{insert_key}{{worker="{w}"}}' in mf_body
            assert f'slo_degraded{{worker="{w}"}}' in mf_body
        publishes = [counter_of(
            http_get(f"http://127.0.0.1:{p}/metrics")[1],
            "fleet_obs_publishes") for p in mports]
        assert min(publishes) > 0

        wait_for("the query plane",
                 lambda: (http_get(f"http://127.0.0.1:{qport}/healthz")[0]
                          == 200) or None, 60)
        trace.enable(str(tmp_path / "client-trace.json"))
        try:
            client = QueryClient(f":{qport}", timeout_s=10.0)
            for i in range(n_queries):
                assert "results" in client.query_one(
                    "obs-issuer", "2031-06-15", f"0bad{i:04x}")
            client_doc_path = trace.export()
        finally:
            trace.disable()
        with open(client_doc_path) as fh:
            client_doc = json.load(fh)

        os.kill(procs[1].pid, signal.SIGSTOP)
        try:
            def degraded():
                st, raw = http_get(fleet_url)
                return json.loads(raw) if st == 503 and raw else None

            flip = wait_for("the rollup to turn 503", degraded, 60)
        finally:
            os.kill(procs[1].pid, signal.SIGCONT)
        assert any("worker 1" in r for r in flip.get("degraded", [])), flip
        wait_for("the rollup to recover", healthy_rollup, 90)

        for p in procs:  # a clean stop: each worker exports its ring
            os.kill(p.pid, signal.SIGTERM)
        outs = [p.communicate(timeout=180)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                os.kill(p.pid, signal.SIGCONT)
                p.kill()
                p.wait(timeout=10)
        server.stop()
    for w, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, (w, out[-1500:])

    docs = []
    for path in trace_paths:
        with open(path) as fh:
            docs.append(json.load(fh))
    assert all(any(e.get("ph") in ("X", "i") for e in d["traceEvents"])
               for d in docs)
    merged = fleetobs.merge_traces([client_doc] + docs)
    events = [e for e in merged["traceEvents"] if e.get("ph") != "M"]
    assert len({e.get("pid") for e in events}) >= 3
    labels = {e["args"]["name"] for e in merged["traceEvents"]
              if e.get("ph") == "M" and e.get("name") == "process_name"}
    for want in ("worker 0 (", "worker 1 ("):
        assert any(lab.startswith(want) for lab in labels), labels
    minted = {e["args"]["trace_id"] for e in client_doc["traceEvents"]
              if e.get("name") == "query.client"
              and "trace_id" in e.get("args", {})}
    assert len(minted) == n_queries
    assert any(e.get("args", {}).get("trace_id") in minted
               and e.get("pid") != os.getpid() for e in events)


@pytest.mark.parametrize("scenario,kill_env", [
    # Die right after the delta segment's rename, BEFORE the manifest
    # update: the durable chain is still the pre-tick one; the stray
    # renamed segment is unlisted and must be ignored (and later
    # overwritten) by the resumed worker.
    ("mid-segment", {"CTMR_CKPT_KILL": "seg-post-rename"}),
    # Die inside a COMPACTION, after the fresh anchor base's rename
    # but before its fresh manifest: the old manifest's baseSha256 no
    # longer matches the on-disk base, so the loader must heal to
    # base-alone (the anchor IS the full state at its cut).
    ("mid-compaction", {"CTMR_CKPT_KILL": "base-post-rename:2",
                        "CTMR_CKPT_MAX_CHAIN": "1"}),
])
@pytest.mark.timeout(340)
def test_fleet_kill_points_ck02(tmp_path, compile_cache, scenario,
                                kill_env):
    """ISSUE 18 acceptance: a worker SIGKILLed at the exact CTMRCK02
    write boundaries (mid-delta-segment, mid-compaction) leaves a
    chain that VALIDATES and restores to the last durable tick, and a
    restarted worker resumes through it to the uninterrupted run's
    aggregate. The self-kill rides ckpt.kill_point (CTMR_CKPT_KILL),
    so death lands deterministically at the boundary under test —
    victim cache policy as in the round-14 test (read-only consume).

    The victim reaches its kill point whenever ONE save lands before
    the log's last page is folded: that save is the base, the next one
    with new rows appends a segment (mid-segment dies there), and the
    one after it (a tick, the log's exit save or the round's) must
    compact under maxChain=1 (mid-compaction dies there). The only
    losing order is a first save after the whole log, which leaves one
    base, no-op saves and exit code 0: at a page every 150 ms against
    a 300 ms tick the stream lasted a second and a loaded machine lost
    that race about one run in three. A page a second against a 100 ms
    tick puts six seconds and some sixty idle ticks before the last
    page; the victim still dies after its second or third page."""
    from tools import fleet as harness

    from ct_mapreduce_tpu.agg import ckpt
    from ct_mapreduce_tpu.ingest import ctclient
    from ct_mapreduce_tpu.utils.miniredis import MiniRedis

    fixture_path = str(tmp_path / "fixture.json")
    fixture = harness.build_fixture(
        fixture_path, n_logs=1, entries_per_log=192, dupes=16,
        max_batch=32)
    url = next(iter(fixture["logs"]))
    wdir = str(tmp_path / "w0")
    npz = os.path.join(wdir, "agg.npz")

    server = MiniRedis().start()
    try:
        # spawn_worker forwards os.environ, so the kill spec (and for
        # the compaction case a maxChain=1 override that forces an
        # anchor on the 2nd tick) reaches only the victim child.
        for k, v in kill_env.items():
            os.environ[k] = v
        try:
            victim = harness.spawn_worker(
                0, 1, fixture_path, wdir, server.address,
                checkpoint_period="100ms", throttle_ms=1000,
                coordinator="redis", compile_cache_readonly=True)
            out = victim.communicate(timeout=300)[0]
        finally:
            for k in kill_env:
                os.environ.pop(k, None)
        assert victim.returncode == -signal.SIGKILL, (
            f"{scenario}: victim did not die at the kill point "
            f"(rc={victim.returncode}):\n{out[-4000:]}")

        # Durable contract at the moment of death: the on-disk chain
        # validates and loads — death between the segment/base rename
        # and the manifest update never publishes a torn state.
        chain = ckpt.resolve_chain(npz)
        assert len(chain.segments) == 0, scenario
        # The segment really is on disk: renamed but unlisted, or
        # listed by the stale manifest of the chain the anchor replaced.
        assert os.path.exists(ckpt.segment_path(npz, 1)), scenario
        victim_snap = harness.merged_snapshot([npz])
        assert victim_snap["total"] > 0

        # Resume in-process (round-14 discipline) with the kill spec
        # cleared: the worker must extend/anchor past the stray
        # artifacts and finish with the uninterrupted run's aggregate.
        from ct_mapreduce_tpu.cmd import ct_fetch

        transport = harness.FixtureTransport(fixture)
        orig_transport = ctclient._default_transport
        ctclient._default_transport = transport
        try:
            ini = os.path.join(wdir, "resume.ini")
            harness.write_worker_ini(
                ini, fixture, npz, redis_addr=server.address,
                checkpoint_period="300ms", coordinator="redis")
            rc = ct_fetch.main(["-config", ini, "-nobars"])
        finally:
            ctclient._default_transport = orig_transport
    finally:
        server.stop()
    assert rc == 0
    merged = harness.merged_snapshot([npz])
    ref = harness.run_serial_reference(fixture, str(tmp_path))
    assert merged == ref
    assert merged["total"] > 0


# -- global-mesh collectives (capability-gated) -------------------------


@pytest.mark.timeout(360)
def test_two_process_global_mesh(tmp_path):
    repo = Path(__file__).resolve().parent.parent
    child = tmp_path / "mp_child.py"
    child.write_text(_CHILD)
    port = _free_port()
    import os

    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)  # the child sets its own
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = str(repo)
    env["CT_GRAFT_ENTRY"] = str(repo / "__graft_entry__.py")
    procs = [
        subprocess.Popen(
            [sys.executable, str(child), str(port), str(i), "2"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True, env=env,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-process run timed out")
        outs.append(out)
    # Backend capability gate: some jax/XLA CPU builds (including the
    # one in the CI container) cannot run cross-process collectives at
    # all — every child dies inside its first mesh-global op with
    # "Multiprocess computations aren't implemented on the CPU
    # backend". That is an environment limit, not a regression in the
    # distributed path (the same test passes where the capability
    # exists), so skip with the reason instead of carrying a known-red
    # tier-1 entry. Any OTHER failure still fails loudly.
    cap_msgs = (
        "Multiprocess computations aren't implemented",
        "multiprocess computations aren't implemented",
    )
    if any(p.returncode != 0 for p in procs) and any(
            m in out for out in outs for m in cap_msgs):
        pytest.skip(
            "CPU backend in this jax build cannot run multiprocess "
            "collectives (XLA: \"Multiprocess computations aren't "
            "implemented on the CPU backend\")")
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc{i} failed:\n{out}"
    assert "proc0: leader" in outs[0]
    assert "proc1: follower" in outs[1]
    for i in range(2):
        assert f"proc{i}: barrier released" in outs[i]
        assert f"proc{i}: sharded step OK" in outs[i]
