"""One run of one cell: set-up, the window of fixed work, the drain, and
the comparison with the fixture's arithmetic.

The process that calls :func:`run_cell` holds the chip: it calls
``ct_fetch.main`` itself, in its main thread, and conducts the run from
a second thread. From the program it takes the entry points a user has
(``ct-fetch``, ``storage-statistics``, ``/healthz``) and its telemetry
(the metrics sink's samples, the span tracer); the load comes from
``logserver.py`` and, where the traffic file names further generators,
from ``generators/<kind>.py``: processes of their own on cores of their
own.
"""

from __future__ import annotations

import gc
import http.client
import importlib
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time

import fixture as fx
import prefill

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(ROOT, ".bench_work")  # per-run state; git-ignored
STATE_FILE = "agg.npz"  # aggStatePath's name; its manifest and segments share the prefix
FOLD_SAMPLE = "ct-fetch.completeBatch"  # one sample per batch folded
# The store thread's timers, for the diagnosis line's timeline.
STORE_THREAD = ("ct-fetch.decodeBatch", "ct-fetch.storeCertificate",
                FOLD_SAMPLE)
REPLAY = "log_replay"  # the one generator every traffic file has: logserver.py
LOAD_GAUGE = "aggregator.table_load"  # occupied slots over the table's, at every fold
PREFILL_CACHE = os.path.join(ROOT, ".bench_cache", "prefill")  # git-ignored
# The harness's own numbers; a generator's ``VALUES`` may repeat neither.
VALUES = ("ingest_entries_per_s", "setup_s")


class RunFailed(RuntimeError):
    """The run cannot report: something outside ``correct`` broke."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(port: int, path: str, body: dict | None = None,
              timeout: float = 60.0) -> tuple[int, dict]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        if body is None:
            conn.request("GET", path)
        else:
            conn.request("POST", path, body=json.dumps(body),
                         headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read()
        return resp.status, (json.loads(raw) if raw else {})
    finally:
        conn.close()


def log_spec(traffic: dict, seconds: float, batch: int) -> fx.LogSpec:
    replay = [g for g in traffic["generators"] if g["kind"] == REPLAY]
    if len(replay) != 1:
        raise RunFailed(f"a traffic file has exactly one {REPLAY} generator, "
                        f"not {len(replay)}")
    g = replay[0]
    if g["warmup_entries"] % batch or batch % g["page"]:
        raise RunFailed("warm-up and pages must fill whole batches")
    if g["ramp_batches"] < 1:
        raise RunFailed("the window opens at the ramp's last fold: "
                        "ramp_batches must be 1 or more")
    block = g.get("table_prefill")
    if block is not None and sorted(block) != ["known_share", "load",
                                               "slots_log2"]:
        raise RunFailed("table_prefill has slots_log2, load and known_share, "
                        f"not {sorted(block)}")
    return fx.LogSpec(
        table_prefill=block,
        logs=g["logs"], page=g["page"], dup_share=g["dup_share"],
        leaf_mix=g["leaf_mix"], issuers=g["issuers"], zipf_s=g["zipf_s"],
        warmup_entries=g["warmup_entries"],
        window_entries=fx.window_entries(
            g["window_entries_per_second"], seconds, g["logs"], batch),
        ramp_entries=-(-g["ramp_batches"] // g["logs"]) * g["logs"] * batch,
        tail_entries=-(-g["tail_batches"] // g["logs"]) * g["logs"] * batch)


def load_generators(traffic: dict) -> list[tuple[str, dict, object]]:
    """``(kind, parameters, module)`` for every generator of the traffic
    file beside the log: kind ``K`` is ``generators/K.py``, which runs
    as a :class:`Child` and whose ``summarise`` reads its rows after the
    run. Loads neither JAX nor the program."""
    found, values = [], list(VALUES)
    for g in traffic["generators"]:
        kind = g["kind"]
        if kind == REPLAY:
            continue
        if not (kind.isidentifier() and os.path.isfile(
                os.path.join(HERE, "generators", kind + ".py"))):
            raise RunFailed(f"no generator of kind {kind!r}: "
                            f"benchmark/generators/{kind}.py is not there")
        module = importlib.import_module("generators." + kind)
        values += module.VALUES
        found.append((kind, g, module))
    twice = sorted({v for v in values if values.count(v) > 1})
    if twice:
        raise RunFailed(f"values given twice: {', '.join(twice)}")
    kinds = [kind for kind, _g, _module in found]
    if len(set(kinds)) < len(kinds):
        raise RunFailed(f"a kind of generator given twice: {kinds}")
    return found


class Child:
    """A load generator: a process that reads a spec file, says one
    JSON line when it is ready, and ends when its stdin closes. A
    generator beside the log is told, a JSON line each on its stdin,
    when the program has its warm-up round on disk (``warm``: it warms
    up what its operations use, then says it is ready), when the log
    ``opened`` (it starts its operations), when the round was ``folded``
    (it starts no more) and to ``stop``: it then writes its rows to the
    file its spec names and says so."""

    def __init__(self, script: str, spec: dict, workdir: str,
                 name: str | None = None):
        self.name = name or script
        path = os.path.join(workdir, self.name + ".spec.json")
        with open(path, "w") as fh:
            json.dump(spec, fh)
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("JAX_", "XLA_", "TPU_"))}
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, script), path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, env=env, cwd=ROOT)

    def ready(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise RunFailed(f"{self.name} ended before it was ready "
                            f"(rc {self.proc.poll()})")
        return json.loads(line)

    def tell(self, **message) -> None:
        try:
            self.proc.stdin.write(json.dumps(message).encode() + b"\n")
            self.proc.stdin.flush()
        except OSError:
            raise RunFailed(f"{self.name} ended before the run did "
                            f"(rc {self.proc.poll()})") from None

    def rows(self):
        """What the generator recorded, handed back through a file."""
        self.tell(stop=True)
        with open(self.ready()["rows"]) as fh:
            return json.load(fh)

    def stop(self, timeout: float = 60.0) -> None:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.close()
                self.proc.wait(timeout=timeout)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait()
        self.proc.stdout.close()


class FoldStamper:
    """A fan-out emitter on the program's metrics sink (the place StatsD
    rides): stamps the instant each batch's fold returns, from the
    sample the program already records around it."""

    def __init__(self):
        self.stamps: list[float] = []
        # Every sample and counter the program emits, with its instant:
        # the per-layer readers sum them over the window.
        self.samples: list[tuple[float, str, float]] = []
        self.counters: list[tuple[float, str, float]] = []
        # The table's load as the program states it, with its instants.
        self.loads: list[tuple[float, float]] = []
        self.cond = threading.Condition()

    def add_sample(self, key: str, value: float) -> None:
        now = time.monotonic()
        self.samples.append((now, key, value))
        if key == FOLD_SAMPLE:
            with self.cond:
                self.stamps.append(now)
                self.cond.notify_all()

    def incr_counter(self, key: str, value: float) -> None:
        self.counters.append((time.monotonic(), key, value))

    def set_gauge(self, key: str, value: float) -> None:
        if key == LOAD_GAUGE:
            self.loads.append((time.monotonic(), value))

    def load_at(self, t: float) -> float | None:
        """What the gauge held at ``t``: its last setting up to then."""
        return next((v for at, v in reversed(self.loads) if at <= t), None)

    def wait_for(self, count: int, deadline: float, alive) -> None:
        with self.cond:
            while len(self.stamps) < count:
                if time.monotonic() > deadline:
                    raise RunFailed(f"{len(self.stamps)} of {count} batches "
                                    "folded at the deadline")
                if not alive():
                    raise RunFailed("ct-fetch returned before the window "
                                    "was folded")
                self.cond.wait(0.5)


class CompileLog:
    """What XLA compiled and when, from JAX's monitoring events (copy of
    ``chip_smoke.py::CompileLog``, with a time on each)."""

    def __init__(self):
        import jax.monitoring

        self.events: list[tuple[float, float]] = []  # (t_end, seconds)
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events.append((time.monotonic(), seconds))

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def within(self, lo: float, hi: float) -> int:
        return sum(lo <= t <= hi for t, _ in self.events)


class GcLog:
    def __init__(self):
        self.pauses: list[tuple[float, float, int]] = []
        self._t0 = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.monotonic()
        else:
            now = time.monotonic()
            self.pauses.append((now, now - self._t0, info["generation"]))

    def close(self) -> None:
        gc.callbacks.remove(self._cb)


def write_ini(config: dict, workdir: str, name: str, state_path: str,
              log_urls: list[str], ports: dict[str, int]) -> str:
    """``ports`` maps a port directive to the free port taken for it:
    ``metricsPort`` and those the configuration's ``ports`` key asks for."""
    lines = [f"logList = {', '.join(log_urls)}",
             f"aggStatePath = {state_path}"]
    lines += [f"{key} = {port}" for key, port in ports.items()]
    for key, value in config["directives"].items():
        if isinstance(value, bool):
            value = "true" if value else "false"
        lines.append(f"{key} = {value}")
    ini = os.path.join(workdir, name)
    with open(ini, "w") as fh:
        fh.write("\n".join(lines) + "\n")
    return ini


class Conductor:
    """The second thread of the ct-fetch process: waits for the warm-up
    round, opens the log, stamps the folds, waits for the checkpoint,
    keeps the checkpoint's files as they stand at that instant, reads
    the live counters and sends SIGINT."""

    def __init__(self, *, fixture, spec, config, workdir, trace_on,
                 log_port, metrics_port, compiles, marks, children=()):
        self.fixture = fixture
        self.spec = spec
        self.workdir = workdir
        self.trace_on = trace_on
        self.children = children  # the generators beside the log
        self.log_port = log_port
        self.metrics_port = metrics_port
        self.compiles = compiles
        self.marks = marks  # set-up, itemised: (name, monotonic)
        self.stamper = FoldStamper()
        self.fetch_returned = threading.Event()
        self.failure: BaseException | None = None
        self.t_main_called = 0.0  # set as ct_fetch.main is called
        self.out: dict = {}
        self.batch = int(config["directives"]["batchSize"])

    # -- helpers ---------------------------------------------------------
    def mark(self, name: str) -> float:
        now = time.monotonic()
        self.marks.append((name, now))
        return now

    def alive(self) -> bool:
        return not self.fetch_returned.is_set()

    def healthz(self) -> dict:
        return http_json(self.metrics_port, "/healthz")[1]

    def wait_idle(self, want_pos: dict[int, int], deadline: float) -> float:
        """The first instant /healthz reads stage ``idle`` with every
        log's cursor where ``want_pos`` says."""
        while True:
            if not self.alive():
                raise RunFailed("ct-fetch returned before it was idle")
            if time.monotonic() > deadline:
                raise RunFailed("ct-fetch not idle at the deadline")
            try:
                health = self.healthz()
            except OSError:
                time.sleep(0.1)
                continue
            now = time.monotonic()
            pos = {u: p["pos"] for u, p in health["progress"].items()}
            done = all(
                any(u.endswith(f"/log{k}") and p == want
                    for u, p in pos.items()) or want == 0
                for k, want in want_pos.items())
            if health["stage"] == "idle" and done:
                return now
            time.sleep(0.05)

    def install_stamper(self) -> None:
        from ct_mapreduce_tpu.telemetry import metrics

        metrics.set_sink(metrics.get_sink(), self.stamper)

    def keep_checkpoint(self) -> str:
        """The checkpoint's files as they stand now, hard-linked into a
        directory of their own. The program lands every file by rename,
        so a link keeps this instant's bytes whatever is saved later
        (the next poll's round, the exit save): the report is made from
        what was on disk when the program said it was durable."""
        kept = os.path.join(self.workdir, "durable")
        os.makedirs(kept)
        for name in sorted(os.listdir(self.workdir)):
            path = os.path.join(self.workdir, name)
            if name.startswith(STATE_FILE) and os.path.isfile(path):
                prefill.link_or_copy(path, os.path.join(kept, name))
        return kept

    # -- the run ---------------------------------------------------------
    def run(self) -> None:
        try:
            self._run()
        except BaseException as err:
            self.failure = err
        finally:
            if self.alive():
                os.kill(os.getpid(), signal.SIGINT)

    def _run(self) -> None:
        log0 = self.fixture.logs[0]
        warm_batches = log0.warm // self.batch
        ramp_batches = self.spec.ramp_entries // self.batch
        window_batches = self.spec.window_entries // self.batch
        round_batches = ramp_batches + window_batches \
            + self.spec.tail_entries // self.batch
        deadline = time.monotonic() + 1100.0
        while True:  # the metrics endpoint is up once main has its sink
            try:
                self.healthz()
                break
            except OSError:
                if not self.alive() or time.monotonic() > deadline:
                    raise RunFailed("ct-fetch never served /healthz")
                time.sleep(0.05)
        self.install_stamper()
        self.mark("ct_fetch_serving")
        self.stamper.wait_for(warm_batches, deadline, self.alive)
        self.mark("warmup_folded")
        self.wait_idle({0: log0.warm}, deadline)
        self.mark("warmup_durable")
        if self.children:
            for child in self.children:
                child.tell(warm=time.monotonic())
            for child in self.children:
                child.ready()
            self.mark("generators_ready")

        tracer = None
        if self.trace_on:
            import tracing

            tracer = tracing.WindowTrace(os.path.join(self.workdir, "profile"))
            tracer.start()
            self.mark("trace_started")
        gclog = GcLog()
        t_open = http_json(self.log_port, "/control/open", timeout=300)[1][
            "opened_at"]
        for child in self.children:
            child.tell(opened=t_open)
        self.mark("log_opened")
        self.stamper.wait_for(warm_batches + round_batches,
                              time.monotonic() + 600.0, self.alive)
        folds = list(self.stamper.stamps)[:warm_batches + round_batches]
        for child in self.children:
            child.tell(folded=folds[-1])
        if tracer is not None:
            tracer.stop()
        want = {k: log.total for k, log in enumerate(self.fixture.logs)}
        t_durable = self.wait_idle(want, time.monotonic() + 600.0)
        kept = self.keep_checkpoint()
        gclog.close()
        rows = {child.name: child.rows() for child in self.children}

        # The window: from the fold of the ramp's last batch to the fold
        # of its own last batch, so many whole batch periods of a
        # pipeline that was full before and stays full after (the tail).
        t_first = folds[warm_batches + ramp_batches - 1]
        t_folded = folds[warm_batches + ramp_batches + window_batches - 1]
        stamps = http_json(self.log_port, "/control/stamps")[1]
        pages = sorted((p for p in stamps["pages"] if p[4] >= t_open),
                       key=lambda p: p[4])
        if not pages:
            raise RunFailed("the log served no page after it was opened")
        t_last_page = max(p[4] for p in pages if p[1] + p[2] == want[p[0]])
        self.out.update(
            t_open=t_open, t_first=t_first, t_folded=t_folded,
            t_round_folded=folds[-1], t_last_page=t_last_page,
            t_durable=t_durable, kept=kept, rows=rows,
            folds=folds[warm_batches:], pages=pages,
            all_pages=stamps["pages"], t_main_called=self.t_main_called,
            extra_folds=len(self.stamper.stamps) - len(folds),
            samples=[e for e in self.stamper.samples if e[0] >= t_open],
            counters=[e for e in self.stamper.counters if e[0] >= t_open],
            compiles_in_window=self.compiles.within(t_open, t_durable),
            load_at_warmup_fold=self.stamper.load_at(folds[warm_batches - 1]),
            load_at_durable=self.stamper.load_at(t_durable),
            gc=[p for p in gclog.pauses if t_first <= p[0] <= t_folded],
            tracer=tracer)
        self.out["live"] = self.live_facts()
        self.out["peak_bytes"] = peak_device_bytes()

    def live_facts(self) -> dict:
        """After the checkpoint: what the running process says."""
        from ct_mapreduce_tpu.telemetry import metrics

        health = self.healthz()
        snap = metrics.get_sink().snapshot()
        return {
            "cursor": {u: p["pos"] for u, p in health["progress"].items()},
            "submitted": snap["counters"].get("ct-fetch.insertCertificate"),
            "retries": {k: v for k, v in snap["counters"].items()
                        if k.startswith("ingest.retry")},
            "store_errors": snap["counters"].get("ct-fetch.storeError", 0),
        }


def peak_device_bytes() -> int | None:
    import jax

    stats = [d.memory_stats() for d in jax.devices()]
    return max((s["peak_bytes_in_use"] for s in stats if s), default=None)


def report_child(ini: str) -> dict:
    """``storage-statistics -json`` over the checkpoint ``ini`` names:
    a process that cannot see the chip and reads only the files."""
    env = dict(os.environ, PYTHONPATH=ROOT)
    res = subprocess.run(
        [sys.executable, "-m", "ct_mapreduce_tpu.cmd.storage_statistics",
         "-config", ini, "-json"],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    if res.returncode != 0:
        raise RunFailed(f"storage-statistics rc {res.returncode}: "
                        f"{res.stderr[-500:]}")
    return json.loads(res.stdout)


def compare(fixture: fx.RunFixture, tpl: fx.Templates, spec: fx.LogSpec,
            out: dict, report: dict) -> list[dict]:
    """Every number compared, beside its limit. All are exact. The
    report is of the checkpoint as it stood at ``t_durable``."""
    want_by = fixture.expected_by_issuer()
    ids = tpl.issuer_ids[: spec.issuers]
    got = {i["id"]: i["serials"] for i in report["issuers"]}
    live = out["live"]
    checks = [
        {"what": "durable report: unique serials",
         "got": report["totals"]["serials"], "want": fixture.expected_unique()},
        {"what": "durable report: per-issuer counts that differ",
         "got": sum(got.get(ids[k], 0) != int(want_by[k])
                    for k in range(spec.issuers))
         + len(set(got) - set(ids)), "want": 0},
        {"what": "durable report: expiry groups other than the fixture's",
         "got": len({e for i in report["issuers"] for e in i["expDates"]}
                    - {tpl.exp_date_id}), "want": 0},
        {"what": "live: entries submitted to the device",
         "got": int(live["submitted"] or 0), "want": fixture.offered},
        {"what": "live: cursors short of or past their log's end",
         "got": sum(p != fixture.logs[int(u.rsplit("log", 1)[1])].total
                    for u, p in live["cursor"].items())
         + abs(len(live["cursor"]) - spec.logs), "want": 0},
        {"what": "live: store errors", "got": int(live["store_errors"]),
         "want": 0},
        {"what": "round: batches folded beyond the fixture's",
         "got": out["extra_folds"], "want": 0},
        {"what": "round: programs compiled", "got": out["compiles_in_window"],
         "want": 0},
    ]
    standing = spec.standing
    if standing is not None:
        # The rows the live table held once the warm-up round was
        # folded: the program's gauge of its load then, times the slots.
        log0 = fixture.logs[0]
        slots = prefill.table_slots(standing.slots_log2)
        load = out["load_at_warmup_fold"]
        stood = fixture.standing_by_issuer()
        new = fixture.new_by_issuer()
        checks += [
            {"what": "restore: rows the live table held when the warm-up "
                     "round was folded",
             "got": None if load is None else round(load * slots),
             "want": standing.rows
             + int(log0.unique_by_issuer(0, log0.warm).sum())},
            {"what": "durable report: standing rows missing",
             "got": sum(max(0, int(stood[k]) - (got.get(ids[k], 0)
                                                - int(new[k])))
                        for k in range(spec.issuers)), "want": 0},
        ]
    for c in checks:
        c["ok"] = c["got"] == c["want"]
    return checks


def place_base(spec: fx.LogSpec, seed: int, config: dict, state_path: str,
               workers: int) -> float | None:
    """Where the traffic file has a ``table_prefill`` block: the
    standing table's base checkpoint where ``aggStatePath`` points, as
    a restarted tailer finds its own; built first if this checkout has
    none yet. Returns the seconds the build took (0.0: it was cached),
    None for a cell without the block. The table is not the seed's; the
    controls that stand in for this function want it (tests/breaks.py)."""
    standing = spec.standing
    if standing is None:
        return None
    bits = int(config["directives"]["tableBits"])
    if standing.slots_log2 != bits:
        raise RunFailed(f"table_prefill's slots_log2 {standing.slots_log2} "
                        f"is not the configuration's tableBits {bits}")
    tpl = fx.Templates()
    path, built_s = prefill.ensure(
        PREFILL_CACHE, standing, tpl.issuer_ids,
        prefill.exp_hour_of(tpl.not_after), workers)
    prefill.put(path, state_path)
    return built_s


class Prepared:
    """What a run needs before JAX is loaded: its work directory, its
    logs' sizes, its generators by kind, a free port for every port
    directive, and the log server, started first because it builds
    every page of the run before it says it is ready. Whatever is wrong
    with the cell's files fails here."""

    def __init__(self, config: dict, traffic: dict, *, seed: int,
                 seconds: float, loadgen_cores: list[int]):
        shutil.rmtree(WORK, ignore_errors=True)
        os.makedirs(WORK)
        self.config, self.seed, self.seconds = config, seed, seconds
        self.cores = loadgen_cores
        self.batch = int(config["directives"]["batchSize"])
        self.spec = log_spec(traffic, seconds, self.batch)
        self.generators = load_generators(traffic)
        names = ["metricsPort", *config.get("ports", [])]
        twice = [n for n in names if names.count(n) > 1
                 or n in config["directives"]]
        if twice:
            raise RunFailed(f"port directives given twice: {sorted(set(twice))}")
        self.ports = {name: free_port() for name in names}
        self.children: list[Child] = []
        self.logsrv = Child("logserver.py", {
            "seed": seed, "log_spec": self.spec.__dict__,
            "cores": loadgen_cores}, WORK)
        # Beside the log server's building of its pages and before JAX
        # is loaded: the standing table, if the cell has one.
        try:
            self.prefill_built_s = place_base(
                self.spec, seed, config, os.path.join(WORK, STATE_FILE),
                workers=len(os.sched_getaffinity(0)))
        except BaseException:
            self.close()
            raise

    def start_generators(self, log_port: int) -> list[Child]:
        """Once the log server listens: each generator beside it gets
        its own parameters, the run's seed and length, the logs' sizes,
        the ports by name and the file to leave its rows in."""
        for name, params, _module in self.generators:
            self.children.append(Child(
                os.path.join("generators", params["kind"] + ".py"), {
                    "generator": params, "seed": self.seed,
                    "seconds": self.seconds, "log_spec": self.spec.__dict__,
                    "ports": self.ports, "log_port": log_port,
                    "cores": self.cores,
                    "rows": os.path.join(WORK, name + ".rows.json")},
                WORK, name=name))
        return self.children

    def close(self) -> None:
        for child in [self.logsrv, *self.children]:
            child.stop()


def run_cell(prep: Prepared, *, trace_on: bool, t_start: float,
             device: dict) -> dict:
    """Set up, run the window, drain, compare. Returns the result line's
    fields plus ``diagnosis`` and ``setup`` for the lines before it."""
    marks: list[tuple[str, float]] = [("process_start", t_start)]
    config, spec, seed, batch = prep.config, prep.spec, prep.seed, prep.batch
    conductor = None
    try:
        import jax  # noqa: F401  (the device was checked by the caller)

        from ct_mapreduce_tpu import native
        from ct_mapreduce_tpu.cmd import ct_fetch

        compiles = CompileLog()
        marks.append(("jax_ready", time.monotonic()))
        if not native.available():
            raise RunFailed("ctmr_native.cpp did not build")
        marks.append(("native_ready", time.monotonic()))
        fixture = fx.RunFixture(spec, seed)
        tpl = fx.Templates()
        marks.append(("fixture_ready", time.monotonic()))
        log_port = prep.logsrv.ready()["port"]
        marks.append(("log_server_ready", time.monotonic()))
        metrics_port = prep.ports["metricsPort"]
        urls = [f"http://127.0.0.1:{log_port}/log{k}"
                for k in range(spec.logs)]
        ini = write_ini(config, WORK, "ct-fetch.ini",
                        os.path.join(WORK, STATE_FILE), urls, prep.ports)
        headroom = None
        if trace_on:
            import tracing

            tracing.enable_program_spans()
            headroom = loadgen_rate(log_port, spec)
        conductor = Conductor(
            fixture=fixture, spec=spec, config=config, workdir=WORK,
            trace_on=trace_on, log_port=log_port, metrics_port=metrics_port,
            compiles=compiles, marks=marks,
            children=prep.start_generators(log_port))
        thread = threading.Thread(target=conductor.run, name="bench-conductor")
        # ct-fetch puts back the handler it found: a SIGINT that lands
        # just after it has returned must find this one, not Python's.
        signal.signal(signal.SIGINT, lambda *_: None)
        thread.start()
        conductor.t_main_called = time.monotonic()
        try:
            rc = ct_fetch.main(["-config", ini, "-nobars"])
        finally:
            conductor.fetch_returned.set()
            thread.join()
            signal.signal(signal.SIGINT, signal.default_int_handler)
        if conductor.failure is not None:
            raise conductor.failure
        if rc != 0:
            raise RunFailed(f"ct-fetch exited {rc}")
    finally:
        prep.close()

    out = conductor.out
    report = report_child(write_ini(
        config, WORK, "report.ini", os.path.join(out["kept"], STATE_FILE),
        urls, prep.ports))
    checks = compare(fixture, tpl, spec, out, report)
    n = spec.window_entries
    values = {
        "ingest_entries_per_s": n / (out["t_folded"] - out["t_first"]),
        "setup_s": out["t_first"] - t_start,
    }
    in_round = spec.ramp_entries + n + spec.tail_entries
    failed_entries = in_round - min(in_round, len(out["folds"]) * batch)
    if not all(c["ok"] for c in checks[:2]):
        failed_entries = max(failed_entries, abs(
            fixture.expected_unique() - report["totals"]["serials"]))
    parts = {REPLAY: {"attempted": n, "failed": min(n, failed_entries)}}
    generator_notes: dict[str, dict] = {}
    window = {k: out[k] for k in ("t_open", "t_first", "t_folded")}
    window["pages"] = out["all_pages"]
    for name, params, module in prep.generators:
        try:
            part = module.summarise(out["rows"][name],
                                    dict(window, generator=params),
                                    fixture, config)
        except ValueError as err:
            raise RunFailed(f"{name}: {err}") from None
        if sorted(part["values"]) != sorted(module.VALUES):
            raise RunFailed(f"{name} gave {sorted(part['values'])}, not the "
                            f"values it states: {sorted(module.VALUES)}")
        values.update(part["values"])
        checks += [{"what": f"{name}: {c['what']}", "got": c["got"],
                    "want": c["want"], "ok": c["got"] == c["want"]}
                   for c in part["checks"]]
        parts[name] = {"attempted": part["attempted"],
                       "failed": part["failed"]}
        generator_notes[name] = part.get("diagnosis", {})
    t0 = out["t_first"]
    # Between two pages, as the log server saw it: response written to
    # next request read, (instant, seconds).
    gaps = [(b[3], b[3] - a[5]) for a, b in zip(out["pages"], out["pages"][1:])]
    serve = [p[5] - p[3] for p in out["pages"]]
    diagnosis = {
        "seconds_from_window_open": {
            "log_opened": out["t_open"] - t0,
            "folds": [f - t0 for f in out["folds"]],
            "window_folded": out["t_folded"] - t0,
            "last_page": out["t_last_page"] - t0,
            "round_folded": out["t_round_folded"] - t0,
            "durable": out["t_durable"] - t0},
        "ramp_window_tail_entries": [spec.ramp_entries, n, spec.tail_entries],
        "pages": len(out["pages"]),
        "page_client_gap_ms": {q: fx.quantile([g for _, g in gaps], q / 100) * 1e3
                               for q in (50, 95, 99, 100)} if gaps else {},
        "page_server_ms": {q: fx.quantile(serve, q / 100) * 1e3
                           for q in (50, 95, 100)},
        "store_thread": [[round(t - t0, 3), key.split(".")[1], round(v, 3)]
                         for t, key, v in out["samples"]
                         if key in STORE_THREAD],
        "longest_page_gaps": sorted(
            ([round(t - t0, 3), round(g * 1e3, 1)] for t, g in gaps),
            key=lambda g: -g[1])[:12],
        "retries": out["live"]["retries"],
        "gc_pauses": {"count": len(out["gc"]),
                      "seconds": sum(p[1] for p in out["gc"]),
                      "longest": max((p[1] for p in out["gc"]), default=0.0)},
        "compile": {"programs": len(compiles.events),
                    "seconds": sum(s for _, s in compiles.events),
                    "cache_hits": compiles.cache_hits,
                    "cache_misses": compiles.cache_misses},
        **generator_notes,
    }
    names = [m[0] for m in marks] + ["window_open"]
    times = [m[1] for m in marks] + [out["t_first"]]
    setup = {names[i + 1]: times[i + 1] - times[i]
             for i in range(len(times) - 1)}
    if prep.prefill_built_s is not None:
        # Inside jax_ready's share (Prepared is made before JAX loads);
        # 0.0 but in a checkout's first run.
        setup["of_which_standing_table_built"] = prep.prefill_built_s
    return {
        "correct": all(c["ok"] for c in checks),
        "attempted": sum(p["attempted"] for p in parts.values()),
        "failed": sum(p["failed"] for p in parts.values()),
        "by_generator": parts,
        "values": values, "checks": checks, "diagnosis": diagnosis,
        "setup": setup, "out": out, "spec": spec,
        "compiles": compiles, "headroom": headroom, "config": config,
        "device": dict(device, memory_peak_bytes=out["peak_bytes"]),
    }


def loadgen_rate(log_port: int, spec: fx.LogSpec, pages: int = 48) -> float:
    """Entries per second the log server alone serves: warm-up pages
    fetched back to back by one thread that does nothing with them."""
    conn = http.client.HTTPConnection("127.0.0.1", log_port, timeout=30)
    t0 = time.monotonic()
    got = 0
    for k in range(pages):
        start = (k * spec.page) % spec.warmup_entries
        conn.request("GET", f"/log0/ct/v1/get-entries?start={start}"
                            f"&end={start + spec.page - 1}")
        conn.getresponse().read()
        got += spec.page
    conn.close()
    return got / (time.monotonic() - t0)
