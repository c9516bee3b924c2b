#!/usr/bin/env python3
"""Open-loop ``POST /query`` load on the program's query plane, in a
process of its own (no JAX, none of the program's code): independent
clients that each ask after one serial, arriving by a Poisson process
whose schedule follows from the seed alone.

Parameters (the traffic file's entry of kind ``query_poisson``):
``port`` (the port directive the program answers on), ``rate_per_s``,
``known_share`` (of the requests, those that ask after a serial the log
server served at least ``min_age_s`` before the request is due, drawn
Zipf ``zipf_s`` by recency: rank 1 is the newest such entry; the rest
ask after serials no log holds), ``deadline_s``, ``warmup_lanes`` (the
sizes of the bulk requests, of serials never fed and never asked after
again, that it sends before it says it is ready: the query plane
compiles one membership program for each power of two of lanes it meets,
and the window may meet none for the first time).

Which entries are aged the log server's stamps say (polled here, handed
whole to :func:`summarise`); which serial and issuer an entry carries,
and that ``LOG_STRIDE * k + i`` with ``i`` past log ``k``'s end was
never fed, the fixture's arithmetic says.

Every request is a connection of its own and is timed from the instant
it was **due**: a stall of the program delays the requests behind it,
and they count it. A refused (429), late (504, or no answer by the
deadline), broken or wrong answer counts ten deadlines in the quantiles
and one in ``failed``. :func:`summarise` holds every answer to the fixture
(``correct`` is false if one contradicts it, or if a scheduled request
has no row) and counts the requests sent late.

The protocol is ``logserver.py``'s (a spec file, one JSON line when
ready, ends when stdin closes) and four lines on stdin: ``warm`` (the
program has its warm-up round on disk) starts the warm-up, after which
it says it is ready; ``opened`` starts the schedule at that instant,
``folded`` ends it, ``stop`` waits for the answers still due, writes the
rows to the spec's ``rows`` file and says so. What it hands back is
``{"requests": [row, ...], "generator": {...}}``: the second says how
late its own loop ran (a sleep of 10 ms, overslept by how much) and the
processor time it used, so that a starved generator is not read as a
slow program. A row is ``[due, sent, answered, status, known, log,
entry, answer]`` on ``time.monotonic()``: ``status`` the HTTP status, 0
for a broken exchange, -1 for none by the deadline; ``known`` 1 for a
fed-and-aged serial; ``answer`` the ``known`` flag that came back.
"""

from __future__ import annotations

import asyncio
import bisect
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import fixture as fx  # noqa: E402

VALUES = ("query_p50_ms", "query_p95_ms", "query_p99_ms", "query_sent",
          "query_failed", "query_sent_late")
# A request handed to the socket later than this after it was due was
# sent late: by this process, or by a machine that froze it. It is
# timed from when it was due all the same, so a late send can only make
# the program read slower, never faster: it is counted and reported
# (``query_sent_late``), and does not decide ``correct``.
ON_TIME_S = 0.05
CHUNK = 4096  # arrivals drawn at a time
NEVER_FED_SPAN = 1 << 24  # entry numbers past a log's end to draw from


class Schedule:
    """Arrivals from the seed: for each, seconds after the log opened,
    whether it asks after a known serial, and two uniform draws (the
    recency rank; the log and, for a serial never fed, which)."""

    def __init__(self, seed: int, params: dict):
        self.rng = np.random.default_rng([int(seed), 0x71756572])
        self.rate = float(params["rate_per_s"])
        self.known_share = float(params["known_share"])
        self.at = 0.0

    def chunk(self) -> list[tuple[float, bool, float, float]]:
        offsets = self.at + np.cumsum(self.rng.exponential(1.0 / self.rate,
                                                           CHUNK))
        self.at = float(offsets[-1])
        known = self.rng.random(CHUNK) < self.known_share
        return list(zip(offsets.tolist(), known.tolist(),
                        self.rng.random(CHUNK).tolist(),
                        self.rng.random(CHUNK).tolist()))


class Aged:
    """Per log, how many of its entries the log server had served by an
    instant: pages leave in order, so a prefix."""

    def __init__(self, logs: int):
        self.done: list[list[float]] = [[] for _ in range(logs)]
        self.upto: list[list[int]] = [[] for _ in range(logs)]

    def add(self, pages: list) -> None:
        """``logserver.py``'s stamps, ordered by the instant each
        response was written out."""
        for k, start, count, _t_req, _t_resp, t_done in sorted(
                pages, key=lambda p: p[5]):
            upto = self.upto[k]
            self.done[k].append(t_done)
            upto.append(max(start + count, upto[-1] if upto else 0))

    def entries(self, log: int, instant: float) -> int:
        n = bisect.bisect_right(self.done[log], instant)
        return self.upto[log][n - 1] if n else 0


class Draw:
    """From a schedule's uniforms to the serial a request asks after."""

    def __init__(self, spec: fx.LogSpec, seed: int, params: dict):
        self.run = fx.RunFixture(spec, seed)
        self.tpl = fx.Templates()
        self.min_age = float(params["min_age_s"])
        most = max(log.total for log in self.run.logs)
        self.cum = np.cumsum(
            1.0 / np.arange(1, most + 1, dtype=np.float64) ** params["zipf_s"])

    def pick(self, aged: Aged, due: float, known: bool, u_rank: float,
             u_which: float) -> tuple[int, int, int]:
        """``(known, log, entry)``; a request for a known serial while
        nothing has aged yet asks after one never fed instead."""
        logs = self.run.logs
        ready = [k for k in range(len(logs))
                 if aged.entries(k, due - self.min_age) > 0]
        if known and ready:
            k = ready[min(len(ready) - 1, int(u_which * len(ready)))]
            n = aged.entries(k, due - self.min_age)
            rank = int(np.searchsorted(self.cum, u_rank * self.cum[n - 1])) + 1
            return 1, k, n - min(rank, n)
        k = min(len(logs) - 1, int(u_which * len(logs)))
        return 0, k, logs[k].total + int(u_rank * NEVER_FED_SPAN)

    def body(self, log: int, entry: int) -> dict:
        fixture = self.run.logs[log]
        if entry < fixture.total:
            serial = int(fixture.serial_of[entry])
            issuer = int(fixture.issuer_of[entry])
        else:
            serial = log * fx.LOG_STRIDE + entry
            issuer = entry % fixture.spec.issuers
        return {"issuer": self.tpl.issuer_ids[issuer],
                "expDate": self.tpl.exp_date_id,
                "serial": "4d" + "%030x" % serial}


def post(doc: dict) -> bytes:
    body = json.dumps(doc).encode()
    return (b"POST /query HTTP/1.1\r\nHost: x\r\nConnection: close\r\n"
            b"Content-Type: application/json\r\nContent-Length: %d\r\n\r\n"
            % len(body)) + body


def status_and_body(raw: bytes) -> tuple[int, dict]:
    head, _, payload = raw.partition(b"\r\n\r\n")
    return int(head.split(None, 2)[1]), json.loads(payload)


def summarise(handed: dict, window: dict, fixture: fx.RunFixture,
              config: dict) -> dict:
    """The requests due inside ``(t_first, t_folded]``, each answer held
    to the fixture and to the log server's stamps as the harness read
    them (not to what the generator believed when it sent it)."""
    params = window["generator"]
    deadline, min_age = params["deadline_s"], params["min_age_s"]
    aged = Aged(len(fixture.logs))
    aged.add(window["pages"])
    lo, hi = window["t_first"], window["t_folded"]
    mine = [r for r in handed["requests"] if lo < r[0] <= hi]
    if not mine:
        raise ValueError("no request was due inside the window")
    # The schedule again, from the seed: every arrival due inside the
    # window has to have a row.
    schedule, planned = Schedule(fixture.seed, params), []
    while not planned or planned[-1] <= hi:
        planned += [window["t_open"] + arrival[0]
                    for arrival in schedule.chunk()]
    unsent = sum(lo < t <= hi for t in planned) - len(mine)
    latencies, failed, wrong, late, misdrawn = [], 0, 0, 0, 0
    statuses: dict[str, int] = {}
    for due, sent, answered, status, known, log, entry, answer in mine:
        statuses[str(status)] = statuses.get(str(status), 0) + 1
        late += sent - due > ON_TIME_S
        misdrawn += (entry >= aged.entries(log, due - min_age) if known
                     else entry < fixture.logs[log].total)
        contradicts = status == 200 and answer is not bool(known)
        wrong += contradicts
        if status == 200 and not contradicts and answered - due <= deadline:
            latencies.append(answered - due)
        else:
            failed += 1
            latencies.append(10.0 * deadline)
    return {
        "attempted": len(mine), "failed": failed,
        "checks": [
            {"what": "answers that contradict the fixture (a fed-and-aged "
                     "serial unknown, a never-fed one known)",
             "got": wrong, "want": 0},
            {"what": "requests the generator never sent or drew from the "
                     "wrong serials", "got": abs(unsent) + misdrawn,
             "want": 0}],
        "diagnosis": {
            "statuses": statuses, "unsent": unsent, "sent_late": late,
            "misdrawn": misdrawn, "asked_known": sum(r[4] for r in mine),
            "generator": handed["generator"],
            "sent_after_due_ms": {
                str(q): fx.quantile([r[1] - r[0] for r in mine], q / 100) * 1e3
                for q in (50, 99, 100)}},
        "values": {
            "query_p50_ms": fx.quantile(latencies, 0.50) * 1e3,
            "query_p95_ms": fx.quantile(latencies, 0.95) * 1e3,
            "query_p99_ms": fx.quantile(latencies, 0.99) * 1e3,
            "query_sent": float(len(mine)), "query_failed": float(failed),
            "query_sent_late": float(late)},
    }


# -- the process ------------------------------------------------------------

def main(argv: list[str]) -> int:
    with open(argv[0]) as fh:
        doc = json.load(fh)
    if doc.get("cores"):
        os.sched_setaffinity(0, doc["cores"])
    params = doc["generator"]
    port = doc["ports"][params["port"]]
    deadline = float(params["deadline_s"])
    draw = Draw(fx.LogSpec(**doc["log_spec"]), doc["seed"], params)
    schedule = Schedule(doc["seed"], params)
    aged = Aged(len(draw.run.logs))
    rows: list[list] = []
    lags: list[float] = []
    loop = asyncio.new_event_loop()
    state = {"opened": None, "folded": False, "polled": 0, "cpu": 0.0}
    inflight: set[asyncio.Task] = set()
    stopped = asyncio.Event()

    async def exchange(to: int, request: bytes, until: float) -> bytes:
        """One HTTP exchange on a connection of its own, given up at
        ``until``."""
        async def talk() -> bytes:
            reader, writer = await asyncio.open_connection("127.0.0.1", to)
            try:
                writer.write(request)
                await writer.drain()
                return await reader.read(-1)
            finally:
                writer.close()
        return await asyncio.wait_for(talk(), max(0.001, until - loop.time()))

    async def poll_stamps() -> None:
        every = min(0.25, draw.min_age / 4)
        while not stopped.is_set():
            raw = await exchange(
                doc["log_port"],
                b"GET /control/stamps?since=%d HTTP/1.1\r\nHost: x\r\n"
                b"Connection: close\r\n\r\n" % state["polled"],
                loop.time() + 30.0)
            pages = json.loads(raw.split(b"\r\n\r\n", 1)[1])["pages"]
            state["polled"] += len(pages)
            aged.add(pages)
            await asyncio.sleep(every)

    async def ask(due: float, known: bool, u_rank: float,
                  u_which: float) -> None:
        kind, log, entry = draw.pick(aged, due, known, u_rank, u_which)
        request = post(dict(draw.body(log, entry),
                            timeoutMs=int(deadline * 1e3)))
        sent = loop.time()
        status, answer = 0, None
        try:
            status, doc = status_and_body(
                await exchange(port, request, due + deadline))
            if status == 200:
                answer = doc["known"]
        except asyncio.TimeoutError:
            status = -1
        except Exception:  # broken, whatever broke it: every request has a row
            status = 0
        rows.append([due, sent, loop.time(), status, kind, log, entry, answer])

    async def warm_up() -> None:
        """One bulk request for each of ``warmup_lanes``, one after the
        other, so that each is a batch of its own; then ready."""
        entry = draw.run.logs[0].total + NEVER_FED_SPAN
        for lanes in params["warmup_lanes"]:
            request = post({"queries": [draw.body(0, entry + j)
                                        for j in range(lanes)]})
            entry += lanes
            status, doc = status_and_body(
                await exchange(port, request, loop.time() + 600.0))
            if status != 200:  # what it says is the window's to judge
                sys.exit(f"query_poisson: warm-up of {lanes} lanes: "
                         f"{status} {str(doc)[:200]}")
        print(json.dumps({"ready": True}), flush=True)

    async def arrivals() -> None:
        while not state["folded"]:
            for offset, known, u_rank, u_which in schedule.chunk():
                due = state["opened"] + offset
                await asyncio.sleep(max(0.0, due - loop.time()))
                if state["folded"]:
                    return
                task = loop.create_task(ask(due, known, u_rank, u_which))
                inflight.add(task)
                task.add_done_callback(inflight.discard)

    async def watch_lag() -> None:
        """How much a sleep of 10 ms oversleeps: this loop's own delay."""
        while True:
            before = loop.time()
            await asyncio.sleep(0.01)
            lags.append(loop.time() - before - 0.01)

    async def hand_back() -> None:
        if inflight:
            await asyncio.wait(set(inflight), timeout=deadline + 1.0)
        since = state["opened"] or loop.time()
        mine = [lag for lag in lags if lag > 0.0] or [0.0]
        with open(doc["rows"], "w") as fh:
            json.dump({
                "requests": sorted(rows, key=lambda r: r[0]),
                "generator": {
                    "loop_late_ms": {str(q): fx.quantile(mine, q / 100) * 1e3
                                     for q in (50, 99, 100)},
                    "cpu_s": time.process_time() - state["cpu"],
                    "wall_s": loop.time() - since}}, fh)
        print(json.dumps({"rows": doc["rows"], "requests": len(rows)}),
              flush=True)

    tasks: list[asyncio.Task] = []

    def told(message: dict) -> None:
        if "warm" in message:
            tasks.append(loop.create_task(warm_up()))
        elif "opened" in message and state["opened"] is None:
            state["opened"] = float(message["opened"])
            state["cpu"] = time.process_time()
            lags.clear()
            tasks.append(loop.create_task(arrivals()))
        elif "folded" in message:
            state["folded"] = True
        elif "stop" in message:
            state["folded"] = True
            tasks.append(loop.create_task(hand_back()))

    def stdin_lines() -> None:
        # The parent closes our stdin to end us.
        for line in sys.stdin:
            loop.call_soon_threadsafe(told, json.loads(line))
        loop.call_soon_threadsafe(stopped.set)

    async def run() -> None:
        tasks.append(loop.create_task(poll_stamps()))
        tasks.append(loop.create_task(watch_lag()))
        threading.Thread(target=stdin_lines, daemon=True).start()
        await stopped.wait()
        for task in [*tasks, *inflight]:
            task.cancel()

    loop.run_until_complete(run())
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
