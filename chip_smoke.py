#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, on one TPU v5e, through the entry points a
CT auditor uses, at the size one would call real:

  loopback CT log (1,048,576 wire entries, 16 issuers, ~3% seeded
  duplicate serials, pages built on demand from the seed)
    → ``ct-fetch`` (``backend = tpu``, 2^26-slot table resident in HBM,
      65,536-lane batches, checkpoint, query plane, ``runForever``)
    → ``ct-query`` against the live plane (fed serials known, unfed
      unknown, per-issuer metadata) and the plane's ``/healthz``
    → SIGINT → ``storage-statistics -json``

and checks the answers against the fixture's own arithmetic (N − D
unique serials, per-issuer counts from the seed) — no repo code in the
reference. Every line of stdout is one JSON object; the last one is
``{"ok": true, "device": {...}}`` with the device as JAX reports it.

One process holds the chip: this script calls ``ct_fetch.main`` itself
and serves the log from a thread. Its children (``ct-query``,
``storage-statistics``) are host-only readers, which is part of what
is being proven — a child that reached for the chip would fail here.

``--chips 4`` runs only the mesh-sharded drive (``meshShape =
shard:4``) and its comparison, and asserts every chip holds a shard.

There is no option that lets this pass without a TPU. Rehearsals
import the phase functions below from a scratch script at tiny sizes.
"""

from __future__ import annotations

import argparse
import base64
import datetime
import glob
import hashlib
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from urllib.parse import parse_qs, urlparse

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".chip_smoke")  # state of one run; git-ignored

# Entries per get-entries response. Real logs cap pages far below the
# 1000 a client asks for; 512 also makes 128 pages fill one 65,536-lane
# batch exactly, so the whole ingest is dispatches of ONE program.
PAGE = 512
ISSUERS = 16
DUP_SHARE = 0.03
NOT_AFTER = datetime.datetime(2031, 6, 15, 14, tzinfo=datetime.timezone.utc)
EXP_DATE_ID = NOT_AFTER.strftime("%Y-%m-%d-%H")
# A compile that takes longer than this is one of the ingest programs
# (minutes each); everything else on the path compiles in seconds.
BIG_COMPILE_S = 30.0


def emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


# -- phases ---------------------------------------------------------------


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it, from inside the process that will
    hold the chip. Anything but ``chips`` TPU devices ends the run
    before any work."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" or device["count"] != chips:
        sys.exit(f"chip_smoke: needs {chips} TPU device(s), JAX found "
                 f"{device}")
    return device


def build_native() -> float:
    """Throw away whatever library a previous tree left behind and
    build ``ctmr_native.cpp`` from source; the Python lanes the library
    would quietly degrade to are not accepted here."""
    from ct_mapreduce_tpu import native

    for stale in glob.glob(os.path.join(
            ROOT, "ct_mapreduce_tpu", "native", "libctmr_native.so*")):
        os.unlink(stale)
    t0 = time.monotonic()
    if not native.available():
        raise RuntimeError("ctmr_native.cpp did not build")
    return time.monotonic() - t0


class CompileLog:
    """Counts what XLA compiled and what the persistent cache served,
    from JAX's own monitoring events."""

    def __init__(self):
        import jax.monitoring

        self.durations: list[float] = []
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.durations.append(seconds)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def facts(self) -> dict:
        return {
            "programs": len(self.durations),
            "compile_s": round(sum(self.durations), 2),
            "big_programs": sum(d >= BIG_COMPILE_S for d in self.durations),
            "longest_compile_s": round(max(self.durations, default=0.0), 2),
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
        }


class Fixture:
    """N log entries over ``ISSUERS`` issuers, a seeded D of which
    repeat the serial of an earlier entry. Entry ``i`` carries serial
    ``serial_of[i]`` under issuer ``serial_of[i] % ISSUERS``; the
    expected report follows from that arithmetic alone."""

    def __init__(self, n: int, seed: int):
        import numpy as np
        from cryptography import x509
        from cryptography.hazmat.primitives import serialization

        from ct_mapreduce_tpu.utils import syncerts

        rng = np.random.default_rng(seed)
        is_dup = rng.random(n) < DUP_SHARE
        is_dup[0] = False
        originals = np.flatnonzero(~is_dup)
        dups = np.flatnonzero(is_dup)
        # Each duplicate repeats one of the originals that precede it.
        n_earlier = np.searchsorted(originals, dups)
        self.serial_of = np.arange(n, dtype=np.int64)
        self.serial_of[dups] = originals[
            (rng.random(len(dups)) * n_earlier).astype(np.int64)]
        self.n = n
        self.n_dups = int(len(dups))
        self.first_dup_at = int(dups[0])
        self.unique = n - self.n_dups
        self.templates = [
            syncerts.make_template(issuer_cn=f"Smoke Issuer CA {k:02d}",
                                   not_after=NOT_AFTER)
            for k in range(ISSUERS)
        ]
        # issuerID = base64url(SHA-256(SPKI)), computed here from the
        # issuer certificate rather than through the code under test.
        self.issuer_ids = [
            base64.urlsafe_b64encode(hashlib.sha256(
                x509.load_der_x509_certificate(t.issuer_der).public_key()
                .public_bytes(serialization.Encoding.DER,
                              serialization.PublicFormat.SubjectPublicKeyInfo)
            ).digest()).decode()
            for t in self.templates
        ]
        per_issuer = np.bincount(originals % ISSUERS, minlength=ISSUERS)
        self.unique_by_issuer = {
            self.issuer_ids[k]: int(per_issuer[k]) for k in range(ISSUERS)}

    def page(self, start: int, end: int) -> bytes:
        from ct_mapreduce_tpu.utils import syncerts

        end = min(end, start + PAGE - 1, self.n - 1)
        lis, eds = syncerts.make_wire_batch(
            self.templates, start, end - start + 1,
            serials=self.serial_of[start:end + 1])
        return json.dumps({"entries": [
            {"leaf_input": li, "extra_data": ed}
            for li, ed in zip(lis, eds)]}).encode()

    @staticmethod
    def serial_hex(serial: int) -> str:
        """Serial content bytes as the wire carries them: 0x4D, then
        the counter (syncerts.stamp_serial)."""
        return "4d" + serial.to_bytes(15, "big").hex()


class LogServer:
    """The fixture behind a real loopback socket: get-sth and
    get-entries, like a CT log front end."""

    def __init__(self, fixture: Fixture):
        server = self
        self.last_page_at = None  # monotonic time the log's tail was served

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def do_GET(self):
                parsed = urlparse(self.path)
                if parsed.path.endswith("/ct/v1/get-sth"):
                    status, body = 200, json.dumps({
                        "tree_size": fixture.n,
                        "timestamp": 1_700_000_000_000}).encode()
                elif parsed.path.endswith("/ct/v1/get-entries"):
                    q = parse_qs(parsed.query)
                    start, end = int(q["start"][0]), int(q["end"][0])
                    if 0 <= start < fixture.n and end >= start:
                        status, body = 200, fixture.page(start, end)
                        if end >= fixture.n - 1:
                            server.last_page_at = time.monotonic()
                    else:
                        status, body = 400, b"range beyond tree size"
                else:
                    status, body = 404, b"not found"
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *_args):
                pass

        self._httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self._httpd.daemon_threads = True
        self.url = f"http://127.0.0.1:{self._httpd.server_address[1]}/smoke"
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="smoke-log", daemon=True)

    def __enter__(self) -> "LogServer":
        self._thread.start()
        return self

    def __exit__(self, *_exc) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def get_json(url: str) -> dict:
    with urllib.request.urlopen(url, timeout=30) as resp:
        return json.load(resp)


def run_cli(module: str, *argv: str) -> subprocess.CompletedProcess:
    """One of the repo's host-only CLIs as a user runs it: a child
    process, while this process holds the chip."""
    env = dict(os.environ, PYTHONPATH=ROOT)
    return subprocess.run(
        [sys.executable, "-m", f"ct_mapreduce_tpu.cmd.{module}", *argv],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)


def query_live_plane(fixture: Fixture, query_port: int) -> dict:
    """ct-query against the live plane: fed serials are known, unfed
    ones are not, and an issuer's count is the fixture's."""
    addr = f"127.0.0.1:{query_port}"
    facts: dict = {"known": [], "unknown": []}

    def ask(serial: int, want_rc: int, want_known: bool) -> dict:
        res = run_cli("ct_query", "-addr", addr,
                      "-issuer", fixture.issuer_ids[serial % ISSUERS],
                      "-expDate", EXP_DATE_ID,
                      "-serial", Fixture.serial_hex(serial))
        answer = json.loads(res.stdout) if res.returncode == want_rc else {}
        if [r["known"] for r in answer.get("results", ())] != [want_known]:
            raise AssertionError(
                f"serial {serial}: want known={want_known}, got rc "
                f"{res.returncode} {res.stdout} {res.stderr}")
        return answer

    # Spread over the log: its first and last entries, one in between,
    # and one whose serial was fed twice.
    for at in (0, fixture.first_dup_at, fixture.n // 2 + 1, fixture.n - 1):
        serial = int(fixture.serial_of[at])
        answer = ask(serial, 0, True)
        facts["known"].append({"serial": serial, "epoch": answer["epoch"],
                               "staleness_s": answer["staleness_s"]})
    for serial in (fixture.n, fixture.n + 12345, 1 << 40):  # never fed
        ask(serial, 1, False)
        facts["unknown"].append(serial)
    issuer = fixture.issuer_ids[3]
    res = run_cli("ct_query", "-addr", addr, "-issuerMeta", issuer)
    meta = json.loads(res.stdout) if res.returncode == 0 else {}
    if meta.get("unknown_total") != fixture.unique_by_issuer[issuer]:
        raise AssertionError(
            f"-issuerMeta {issuer}: {res.stdout} {res.stderr}, fixture "
            f"says {fixture.unique_by_issuer[issuer]}")
    facts["issuer_meta"] = {"issuer": issuer,
                            "unknown_total": meta["unknown_total"]}
    health = get_json(f"http://{addr}/healthz")
    if (health["device_fallback_total"] != 0 or health["batches_total"] < 1
            or not health["healthy"]):
        raise AssertionError(f"query plane /healthz: {health}")
    facts["device_fallback_total"] = health["device_fallback_total"]
    facts["serve_batches_total"] = health["batches_total"]
    facts["snapshot_epoch"] = health.get("snapshot_epoch")
    return facts


def table_placement(n_devices: int) -> dict:
    """Where the dedup table's rows live, read off the largest live
    device array: ``n_devices`` shards, each on a chip of its own."""
    import jax

    rows = max(jax.live_arrays(), key=lambda a: a.nbytes)
    shard_devices = sorted(str(s.device) for s in rows.addressable_shards)
    if (len(rows.sharding.device_set) != n_devices
            or len(set(shard_devices)) != n_devices):
        raise AssertionError(
            f"table rows {rows.shape} on {shard_devices}, "
            f"want {n_devices} distinct devices")
    return {"table_rows_shape": list(rows.shape),
            "table_bytes": int(rows.nbytes),
            "shard_shapes": sorted({tuple(s.data.shape)
                                    for s in rows.addressable_shards}),
            "shard_devices": shard_devices}


def peak_device_bytes() -> int | None:
    """The most any one device has held so far (table, step
    temporaries, batches in flight, pinned replicas); None on a
    backend that keeps no such count (the CPU of a rehearsal)."""
    import jax

    stats = [d.memory_stats() for d in jax.devices()]
    return max((s["peak_bytes_in_use"] for s in stats if s), default=None)


def drive(fixture: Fixture, log: LogServer, workdir: str, *, table_bits: int,
          batch_size: int, mesh_shape: str = "", query: bool = True,
          deadline_s: float = 1000.0) -> tuple[dict, str]:
    """Run ``ct_fetch.main`` in this thread until a probe thread has
    seen the whole log ingested and checkpointed, asked the live plane
    its questions, and sent SIGINT. Returns what was observed and the
    ini, for the report phase."""
    from ct_mapreduce_tpu.cmd import ct_fetch

    os.makedirs(workdir)
    ini = os.path.join(workdir, "smoke.ini")
    metrics_port = free_port()
    query_port = free_port() if query else 0
    lines = [
        f"logList = {log.url}",
        "backend = tpu",
        f"tableBits = {table_bits}",
        f"batchSize = {batch_size}",
        f"aggStatePath = {os.path.join(workdir, 'agg.npz')}",
        f"metricsPort = {metrics_port}",
        "runForever = true",
        "healthAddr = ",
    ]
    if query:
        lines.append(f"queryPort = {query_port}")
    if mesh_shape:
        lines.append(f"meshShape = {mesh_shape}")
    with open(ini, "w") as fh:
        fh.write("\n".join(lines) + "\n")

    facts: dict = {}
    failure: list[BaseException] = []
    fetch_returned = threading.Event()

    def probe() -> None:
        t0 = time.monotonic()
        t_first = None
        try:
            while True:
                if fetch_returned.is_set():
                    raise RuntimeError("ct-fetch returned before the log "
                                       "was ingested")
                if time.monotonic() - t0 > deadline_s:
                    raise TimeoutError(
                        f"ct-fetch not idle after {deadline_s:.0f}s")
                try:
                    health = get_json(
                        f"http://127.0.0.1:{metrics_port}/healthz")
                except OSError:
                    time.sleep(0.2)  # endpoint not up yet
                    continue
                now = time.monotonic()
                pos = max((p["pos"] for p in health["progress"].values()),
                          default=0)
                if t_first is None and pos > 0:
                    t_first = now
                if health["stage"] == "idle":
                    break
                time.sleep(0.05)
            # First page fetched → idle: download, decode, the device
            # steps (the first one compiles), drain, and the checkpoint
            # of the whole table. The checkpoint cannot be told apart by
            # stage (the cursor-save hook writes it while the stage still
            # reads draining); the tail after the log's last page bounds
            # it from above.
            facts["start_to_first_page_s"] = round(t_first - t0, 2)
            facts["ingest_wall_s"] = round(now - t_first, 2)
            facts["last_page_to_idle_s"] = round(now - log.last_page_at, 2)
            facts["checkpoint_bytes"] = os.path.getsize(
                os.path.join(workdir, "agg.npz"))
            if mesh_shape:
                n_devices = int(mesh_shape.rsplit(":", 1)[1])
                facts.update(table_placement(n_devices))
            if query:
                t_query = time.monotonic()
                facts.update(query_live_plane(fixture, query_port))
                facts["query_phase_s"] = round(time.monotonic() - t_query, 2)
            facts["peak_hbm_bytes"] = peak_device_bytes()
        except BaseException as err:
            failure.append(err)
        finally:
            os.kill(os.getpid(), signal.SIGINT)

    thread = threading.Thread(target=probe, name="smoke-probe")
    thread.start()
    try:
        rc = ct_fetch.main(["-config", ini, "-nobars"])
    finally:
        fetch_returned.set()
        thread.join()
    if failure:
        raise failure[0]
    if rc != 0:
        raise RuntimeError(f"ct-fetch exited {rc}")
    return facts, ini


def serve_timers() -> dict:
    """Seconds the query plane spent swapping a replica in (the
    capture under the locks plus the wait for the copy on the device),
    from the process's own metrics sink."""
    from ct_mapreduce_tpu.telemetry import metrics

    samples = metrics.get_sink().snapshot().get("samples", {})
    out = {}
    s = samples.get("serve.replica_swap_s")
    if s and s.get("count"):
        out["serve.replica_swap_s"] = {
            "count": s["count"],
            "mean_s": round(s["sum"] / s["count"], 3),
            "max_s": round(s["max"], 3)}
    return out


def report(fixture: Fixture, ini: str) -> dict:
    """storage-statistics over the checkpoint ct-fetch left: totals and
    per-issuer counts must equal the fixture's, exactly."""
    res = run_cli("storage_statistics", "-config", ini, "-json")
    if res.returncode != 0:
        raise RuntimeError(f"storage-statistics rc {res.returncode}: "
                           f"{res.stderr}")
    doc = json.loads(res.stdout)
    got = {i["id"]: i["serials"] for i in doc["issuers"]}
    if (doc["totals"]["serials"] != fixture.unique
            or got != fixture.unique_by_issuer):
        raise AssertionError(
            f"report {doc['totals']} {got} != fixture {fixture.unique} "
            f"{fixture.unique_by_issuer}")
    exp = {e for i in doc["issuers"] for e in i["expDates"]}
    if exp != {EXP_DATE_ID}:
        raise AssertionError(f"report expDates {exp} != {EXP_DATE_ID}")
    return {"reported_serials": doc["totals"]["serials"],
            "reported_issuers": doc["totals"]["issuers"],
            "per_issuer_equal": True}


def lowered_step_kernels(ir_dir: str, want: tuple[str, ...]) -> dict:
    """Which kernels and collectives the ingest step that just ran was
    lowered with, from the IR JAX dumped while running it."""
    modules = [p for p in glob.glob(os.path.join(ir_dir, "*"))
               if any(tag in os.path.basename(p)
                      for tag in ("ingest_core", "_local_step"))]
    if not modules:
        raise AssertionError(f"no ingest step among {os.listdir(ir_dir)}")
    for path in modules:
        with open(path) as fh:
            text = fh.read()
        missing = [w for w in want if w not in text]
        if missing:
            raise AssertionError(
                f"{os.path.basename(path)} lowered without {missing}")
    return {"ingest_modules": sorted(os.path.basename(p) for p in modules),
            "contain": list(want)}


# -- the run --------------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=20260926)
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the mesh-sharded drive and its "
                             "comparison (meshShape = shard:4)")
    args = parser.parse_args(argv)

    t_start = time.monotonic()
    device = require_tpu(args.chips)

    import jax

    from ct_mapreduce_tpu.ops import sha256
    from ct_mapreduce_tpu.utils import compile_cache

    emit(phase="device", **device)
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    cache_dir = compile_cache.configure()
    ir_dir = os.path.join(WORK, "ir")
    jax.config.update("jax_dump_ir_to", ir_dir)
    compiles = CompileLog()

    emit(phase="native", native="built-from-source",
         build_s=round(build_native(), 2))

    if args.chips == 1:
        n, table_bits, batch, mesh_shape = 1 << 20, 26, 65536, ""
    else:
        n, table_bits, batch, mesh_shape = 1 << 19, 24, 65536, "shard:4"
    if not sha256._pallas_enabled(batch):
        raise AssertionError(
            f"SHA gate would not pick the Pallas kernel at {batch} lanes")

    t0 = time.monotonic()
    fixture = Fixture(n, args.seed)
    emit(phase="fixture", seed=args.seed, entries=n, duplicates=fixture.n_dups,
         unique=fixture.unique, issuers=ISSUERS, page=PAGE,
         build_s=round(time.monotonic() - t0, 2))

    with LogServer(fixture) as log:
        facts, ini = drive(
            fixture, log, os.path.join(WORK, "run"),
            table_bits=table_bits, batch_size=batch, mesh_shape=mesh_shape,
            query=args.chips == 1)
    emit(phase="ingest", entries_fed=n, table_bits=table_bits,
         batch_size=batch, mesh_shape=mesh_shape or "single chip",
         entries_per_s=round(n / facts["ingest_wall_s"]), **facts)
    if args.chips == 1:
        emit(phase="serve", **serve_timers())

    want = (("tpu_custom_call",) if args.chips == 1
            else ("tpu_custom_call", "all_to_all"))
    emit(phase="kernels", sha_kernel="pallas",
         **lowered_step_kernels(ir_dir, want))
    facts = compiles.facts()
    # A hit is the ingest program coming from the cache: small programs
    # can hit while the one that takes minutes is compiled afresh.
    emit(phase="compile", cache_dir=cache_dir,
         cache_hit=facts["cache_hits"] > 0 and facts["big_programs"] == 0,
         **facts)
    t0 = time.monotonic()
    emit(phase="report", **report(fixture, ini),
         report_s=round(time.monotonic() - t0, 2))
    emit(phase="done", wall_s=round(time.monotonic() - t_start, 1))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
