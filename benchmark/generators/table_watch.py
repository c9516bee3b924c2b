#!/usr/bin/env python3
"""A watcher of the dedup table's size, in a process of its own (no
JAX, none of the program's code): it offers no load. From the log's
opening to the harness's ``stop`` (so past ``folded``: the round's
checkpoint is on disk by then) it asks ``GET /metrics`` on the
program's ``metricsPort`` every ``poll_s`` seconds and keeps, with the
instant, the counter ``aggregator.table_grow`` and the gauges
``aggregator.table_slots`` and ``aggregator.table_load``. The guarantee
**growth** of a configuration whose table crosses its threshold in the
run needs a comparison, and the harness's own ten do not look at the
table's size: :func:`summarise` holds the program to one growth, the
doubled slots and the exact rows.

Parameters (the traffic file's entry of kind ``table_watch``):
``poll_s`` (seconds between two polls), ``answer_within_s`` (a poll
with no answer by then is counted under ``watch.unanswered_polls`` and
fails nothing: the program may be busy growing).

The protocol is ``logserver.py``'s (a spec file, one JSON line when
ready, ends when stdin closes) and four lines on stdin: ``warm`` (there
is nothing to warm up: it says it is ready), ``opened`` starts the
polls, ``folded`` changes nothing, ``stop`` makes one last poll, writes
the rows to the spec's ``rows`` file and says so. A row is ``[asked,
answered, grow, slots, load]`` on ``time.monotonic()``: ``answered``
null for a poll without an answer, and each of the three null where
the program's ``/metrics`` has no such line (a counter never added to
reads 0; a program without the gauge ``aggregator.table_slots`` reads
null there, and is held to it).
"""

from __future__ import annotations

import http.client
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import prefill  # noqa: E402

VALUES = ("watch.grow_seen_s", "watch.unanswered_polls")
# The lines of the program's Prometheus exposition this reads
# (telemetry/promhttp.py writes a dotted name with underscores).
GROW, SLOTS, LOAD = ("aggregator_table_grow", "aggregator_table_slots",
                     "aggregator_table_load")


def parse(text: str) -> list:
    """``[grow, slots, load]`` of one exposition."""
    seen = {}
    for line in text.splitlines():
        name, _, value = line.partition(" ")
        if name in (GROW, SLOTS, LOAD):
            seen[name] = float(value)
    return [seen.get(GROW, 0.0), seen.get(SLOTS), seen.get(LOAD)]


def poll(port: int, within: float) -> list:
    asked = time.monotonic()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=within)
    try:
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        body = resp.read().decode()
        if resp.status != 200 or time.monotonic() - asked > within:
            return [asked, None, None, None, None]
        return [asked, time.monotonic(), *parse(body)]
    except (OSError, http.client.HTTPException, ValueError):
        return [asked, None, None, None, None]
    finally:
        conn.close()


def summarise(handed: dict, window: dict, fixture, config: dict) -> dict:
    """Every poll since the log opened. The checks read the first and
    the last answered poll: the program grew its table once between
    them, the live table has twice the slots the configuration's
    ``tableBits`` build, and it holds the standing rows and every first
    sighting of the run that repeats none, each once (the gauge's load
    times its slots, rounded: both are exact in a float)."""
    polls = [r for r in handed["polls"] if r[0] >= window["t_open"]]
    answered = [r for r in polls if r[1] is not None]
    if not answered:
        raise ValueError("no poll of /metrics was answered after the log "
                         "opened")
    first, last = answered[0], answered[-1]
    want_slots = 2 * prefill.table_slots(
        int(config["directives"]["tableBits"]))
    rows = (None if last[3] is None or last[4] is None
            else round(last[4] * last[3]))
    seen = next((r[1] for r in answered if r[3] == want_slots), None)
    return {
        "attempted": len(polls), "failed": 0,
        "checks": [
            {"what": "growth: grow-and-rehash events between the log's "
                     "opening and the last poll",
             "got": int(last[2] - first[2]), "want": 1},
            {"what": "growth: slots of the live table at the last poll",
             "got": None if last[3] is None else int(last[3]),
             "want": want_slots},
            {"what": "growth: rows in the live table at the last poll",
             "got": rows, "want": fixture.expected_unique()}],
        "diagnosis": {
            "polls": len(polls), "answered": len(answered),
            "first": first, "last": last,
            "slots_seen": sorted({r[3] for r in answered if r[3] is not None}),
            "longest_answer_ms": max(r[1] - r[0] for r in answered) * 1e3},
        "values": {
            "watch.grow_seen_s": (None if seen is None
                                  else seen - window["t_open"]),
            "watch.unanswered_polls": float(len(polls) - len(answered))},
    }


# -- the process ------------------------------------------------------------

def main(argv: list[str]) -> int:
    with open(argv[0]) as fh:
        doc = json.load(fh)
    if doc.get("cores"):
        os.sched_setaffinity(0, doc["cores"])
    params = doc["generator"]
    port = doc["ports"]["metricsPort"]
    every, within = float(params["poll_s"]), float(params["answer_within_s"])
    rows: list[list] = []
    stop = threading.Event()

    def watch() -> None:
        while not stop.is_set():
            rows.append(poll(port, within))
            stop.wait(max(0.0, rows[-1][0] + every - time.monotonic()))

    watcher = None
    for line in sys.stdin:  # the parent closes our stdin to end us
        message = json.loads(line)
        if "warm" in message:
            print(json.dumps({"ready": True}), flush=True)
        elif "opened" in message and watcher is None:
            watcher = threading.Thread(target=watch, daemon=True)
            watcher.start()
        elif "stop" in message:
            stop.set()
            if watcher is not None:
                watcher.join()
            rows.append(poll(port, within))
            with open(doc["rows"], "w") as fh:
                json.dump({"polls": rows}, fh)
            print(json.dumps({"rows": doc["rows"], "polls": len(rows)}),
                  flush=True)
    stop.set()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
