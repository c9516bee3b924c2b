"""Summarize a Chrome trace-event JSON (telemetry/trace.py output)
into per-stage occupancy and gap statistics.

The question a pipelined-ingest trace answers is "which stage starved
which": per span name this prints span count, total busy seconds,
occupancy (busy / trace wall), and the largest gap between consecutive
spans of that stage — a stage with low occupancy and large gaps is
waiting on its upstream; stages whose occupancies sum past 1.0 are
genuinely overlapping. Beside the wall seconds stand the seconds the
stage's threads were on a core (``cpu_s``, the spans' ``tdur``) and off
one (``off_s``: blocked, or waiting for the GIL). Under the stages, one
line a thread family (downloader, store, batcher, front, refresh):
wall under its threads' spans, CPU, off-core, seconds inside native
calls with the GIL released (``native_us``) and seconds spent taking it
back after them (``gil_us``): who holds the GIL. A family is told by
its outermost spans (the front's are ``front.conn`` [requests,
bytes_in, bytes_out], a connection's whole life on the front's one
thread; its ``tdur`` is the CPU of the connection's turns, summed, so
the family's off-core seconds are mostly waits for a batch). The last
line is the
GIL probe's (``gil.probe`` [wait_us]): what a woken thread waited for it.

Usage:
  python tools/traceview.py /tmp/trace.json [--stages name1,name2,...]
  python tools/traceview.py /tmp/trace.json --batch 7
  python tools/traceview.py /tmp/trace.json --batches
  python tools/traceview.py /tmp/trace.json --split fetch.get_entries \
      --against ingest.decode,ingest.submit [--between 6,22]
  python tools/traceview.py --merge w0.json w1.json ... \
      [--skew pairs.json] [--out merged.json]

``--batch N`` prints one ingest batch's lineage: the pages the sink's
cut recorded for it, then every span that carries ``batch=N`` as a
tree by ``parent``, per thread, with each span's self time (its
duration less its children's). ``--batches`` prints one row per batch:
the decode's phases, the submit, the fold's device wait and its
metadata pass, then the decode's, the submit's and the fold's CPU and
off-core milliseconds and what the batch's native calls waited for the
GIL on their return (``gil``).

``--split NAME --against A,B`` is the view of the GIL from before the
spans carried CPU time: every ``NAME`` span goes into one of two groups by whether spans
of the ``--against`` names (on whatever thread) cover at least half of
it, and each group prints its count, the median and mean of its
durations and the mean share covered. A page fetched while the store
thread decodes or submits against one fetched while it waits; a fold
with pages in flight against one without. ``--between LO,HI`` keeps
the spans that start between the cuts of batches LO and HI
(``sink.accumulate`` carries ``batch`` on the span that cuts one).

``--merge`` (round 23) stitches the per-process trace files of a live
``tools/fleet.py`` run into ONE Perfetto-loadable timeline: each file
is shifted onto the corrected wall clock using the (wall, monotonic)
pairs the workers exchanged through the coordinator fabric (``--skew``
is a JSON object ``{"<worker_id>": {"wall": ..., "mono": ...}}`` —
e.g. fleetobs.clock_pairs_from_obs output; without it each trace's own
startup pair is used, exact on a shared-boot host). Tracks are named
per worker/pid, so one ct-query request reads as one flow across both
processes under one ``trace_id``.

Also importable: ``load(path)`` / ``stage_summary(events)`` are the
parsing half of tests/test_trace.py.
"""

from __future__ import annotations

import argparse
import bisect
import json
import statistics
import sys
from collections import defaultdict


def load(path: str) -> list[dict]:
    """Read trace events from either JSON form (object with
    ``traceEvents`` or a bare event array)."""
    with open(path) as fh:
        doc = json.load(fh)
    if isinstance(doc, dict):
        return doc.get("traceEvents", [])
    if isinstance(doc, list):
        return doc
    raise ValueError(f"{path}: not a Chrome trace-event JSON")


def load_doc(path: str) -> dict:
    """Read one trace file as a full doc (``otherData`` kept — the
    merge needs the clock anchors and process attrs)."""
    with open(path) as fh:
        doc = json.load(fh)
    if isinstance(doc, list):
        doc = {"traceEvents": doc, "otherData": {}}
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: not a Chrome trace-event JSON")
    return doc


def merge(paths: list[str], skew_path: str = "",
          out_path: str = "") -> dict:
    """Stitch per-process traces into one skew-corrected doc; writes
    ``out_path`` when given. The correction math lives in
    telemetry/fleetobs.py (unit-tested there)."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from ct_mapreduce_tpu.telemetry import fleetobs

    pairs = None
    if skew_path:
        with open(skew_path) as fh:
            raw = json.load(fh)
        pairs = {int(k): v for k, v in raw.items()
                 if isinstance(v, dict) and "wall" in v and "mono" in v}
    merged = fleetobs.merge_traces([load_doc(p) for p in paths],
                                   pairs=pairs)
    if out_path:
        with open(out_path, "w") as fh:
            json.dump(merged, fh)
    return merged


def complete_spans(events: list[dict]) -> list[dict]:
    """The duration ("X") events, sorted by start timestamp."""
    return sorted(
        (e for e in events if e.get("ph") == "X"),
        key=lambda e: (e.get("ts", 0.0), -e.get("dur", 0.0)),
    )


def stage_summary(events: list[dict], stages=None,
                  t0_us: float = None, t1_us: float = None) -> dict:
    """Per-name span statistics over ``events`` (optionally windowed
    to [t0_us, t1_us] and filtered to ``stages``).

    Returns ``{name: {"count", "busy_s", "cpu_s", "offcore_s",
    "first_us", "last_us", "max_gap_s", "occupancy"}}`` plus a
    ``"_wall_s"`` entry — the span of the whole selection, the
    denominator of every occupancy. ``cpu_s`` and ``offcore_s`` are
    over the spans that carry ``tdur`` (0.0 for a trace from before
    the field).
    Same-name spans never self-nest in this codebase, so per-name busy
    is a plain duration sum (distinct-name nesting does not
    double-count within a name).
    """
    spans = complete_spans(events)
    if t0_us is not None:
        spans = [e for e in spans if e["ts"] >= t0_us]
    if t1_us is not None:
        spans = [e for e in spans if e["ts"] + e.get("dur", 0.0) <= t1_us]
    if stages is not None:
        stages = set(stages)
        spans = [e for e in spans if e["name"] in stages]
    if not spans:
        return {"_wall_s": 0.0}
    wall_us = (max(e["ts"] + e.get("dur", 0.0) for e in spans)
               - min(e["ts"] for e in spans))
    by_name: dict[str, list[dict]] = defaultdict(list)
    for e in spans:
        by_name[e["name"]].append(e)
    out: dict = {"_wall_s": wall_us / 1e6}
    for name, evs in by_name.items():
        busy_us = sum(e.get("dur", 0.0) for e in evs)
        max_gap = 0.0
        prev_end = None
        for e in evs:  # already ts-sorted
            if prev_end is not None:
                max_gap = max(max_gap, e["ts"] - prev_end)
            prev_end = max(prev_end or 0.0, e["ts"] + e.get("dur", 0.0))
        timed = [e for e in evs if "tdur" in e]
        cpu_us = sum(e["tdur"] for e in timed)
        out[name] = {
            "count": len(evs),
            "busy_s": busy_us / 1e6,
            "cpu_s": cpu_us / 1e6,
            "offcore_s": (sum(e.get("dur", 0.0) for e in timed)
                          - cpu_us) / 1e6,
            "first_us": evs[0]["ts"],
            "last_us": prev_end,
            "max_gap_s": max_gap / 1e6,
            "occupancy": (busy_us / wall_us) if wall_us > 0 else 0.0,
        }
    return out


# A thread family by what its outermost spans are called. Not by the
# thread's name: the OS hands a dead thread's ident to the next one, so
# over a run one ``tid`` can carry a downloader's name and then those
# of later threads.
ROOT_FAMILIES = (("downloader", ("fetch.", "round.")),
                 ("store", ("ingest.", "sink.")),
                 ("batcher", ("serve.batch",)),
                 ("front", ("front.",)),
                 ("refresh", ("serve.snapshot", "snapshot.")),
                 ("probe", ("gil.",)))


def family_of(root_name: str) -> str:
    for family, prefixes in ROOT_FAMILIES:
        if root_name.startswith(prefixes):
            return family
    return "other"


def thread_families(events: list[dict]) -> dict:
    """Per thread family ``{"roots", "wall_s", "cpu_s", "offcore_s",
    "native_s", "gil_s"}``: how many outermost spans the family's
    threads recorded, their wall, CPU and off-core seconds (over those
    that carry ``tdur``), and over all the spans under them the seconds
    inside native calls and taking the GIL back after them. The probe's
    spans are in no family (:func:`probe_waits`)."""
    spans = [e for e in complete_spans(events) if "id" in e]
    by_id = {(e["pid"], e["id"]): e for e in spans}
    root_of: dict = {}

    def root(e: dict) -> dict:
        key = (e["pid"], e["id"])
        if key not in root_of:
            up = by_id.get((e["pid"], e.get("parent", 0)))
            root_of[key] = e if up is None else root(up)
        return root_of[key]

    out: dict = {}
    for e in spans:
        top = root(e)
        family = family_of(top["name"])
        if family == "probe":
            continue
        row = out.setdefault(family, {
            "roots": 0, "wall_s": 0.0, "cpu_s": 0.0, "offcore_s": 0.0,
            "native_s": 0.0, "gil_s": 0.0})
        if e is top:
            row["roots"] += 1
            if "tdur" in e:
                row["wall_s"] += e.get("dur", 0.0) / 1e6
                row["cpu_s"] += e["tdur"] / 1e6
                row["offcore_s"] += (e.get("dur", 0.0) - e["tdur"]) / 1e6
        args = e.get("args", {})
        row["native_s"] += args.get("native_us", 0.0) / 1e6
        row["gil_s"] += args.get("gil_us", 0.0) / 1e6
    return out


def nearest_rank(ordered: list[float], percent: int) -> float:
    """The smallest of the ascending values with ``percent`` % of them
    at or under it."""
    return ordered[max(0, -(-percent * len(ordered) // 100) - 1)]


def probe_waits(events: list[dict]) -> list[float]:
    """The GIL probe's ``wait_us`` readings, ascending."""
    return sorted(e["args"]["wait_us"] for e in events
                  if e.get("ph") == "X" and e.get("name") == "gil.probe"
                  and "wait_us" in e.get("args", {}))


def self_us(spans: list[dict]) -> dict:
    """``(pid, id)`` -> the span's duration less its children's, by
    ``parent`` (events of a tracer that records none are left out)."""
    out = {(e["pid"], e["id"]): e.get("dur", 0.0)
           for e in spans if "id" in e}
    for e in spans:
        up = (e["pid"], e.get("parent", 0))
        if up in out:
            out[up] -= e.get("dur", 0.0)
    return out


def batch_lineage(events: list[dict], batch: int) -> list[str]:
    """The lines ``--batch`` prints."""
    spans = complete_spans(events)
    mine = [e for e in spans if e.get("args", {}).get("batch") == batch]
    if not mine:
        return []
    selfs = self_us(spans)
    threads = {e["tid"]: e["args"]["name"] for e in events
               if e.get("ph") == "M" and e.get("name") == "thread_name"}
    t0 = mine[0]["ts"]
    lines = []
    for e in mine:
        for log, first, last in e["args"].get("pages", []):
            lines.append(f"pages  {log} [{first}, {last}]")
    ids = {e["id"] for e in mine}
    kids = defaultdict(list)
    for e in mine:
        kids[e["parent"] if e["parent"] in ids else 0].append(e)

    def walk(e, depth):
        args = {k: v for k, v in e["args"].items()
                if k not in ("batch", "pages")}
        lines.append(
            f"{(e['ts'] - t0) / 1e3:>10.1f} ms  {'  ' * depth}{e['name']}"
            f"  {e['dur'] / 1e3:.2f} ms"
            f" (self {selfs[e['pid'], e['id']] / 1e3:.2f})"
            + (f"  {args}" if args else ""))
        for k in kids[e["id"]]:
            walk(k, depth + 1)

    for tid in dict.fromkeys(e["tid"] for e in kids[0]):
        lines.append(f"thread {threads.get(tid, tid)}")
        for e in kids[0]:
            if e["tid"] == tid:
                walk(e, 1)
    return lines


BATCH_COLUMNS = ("decode.concat_b64", "decode.native_call",
                 "decode.issuer_groups", "decode.pack",
                 "native.decode_batch", "ingest.decode", "ingest.submit",
                 "fold.wait_device", "fold.metadata", "device.fold")
# The spans of a batch whose CPU and off-core time get a column each.
BATCH_CPU_COLUMNS = ("ingest.decode", "ingest.submit", "device.fold")


def batch_table(events: list[dict]) -> list[dict]:
    """One row per batch: milliseconds under each of ``BATCH_COLUMNS``
    (``native.decode_batch`` as self time: what its three children
    leave, the row allocation), with the native call's ``threads`` and
    ``pad``; ``cpu:<name>`` / ``off:<name>`` for ``BATCH_CPU_COLUMNS``
    (milliseconds on a core and off one), ``gil``: what the batch's
    native calls waited for the GIL on their return, and under
    ``issuerCNFilter`` ``cn_drop``: the lanes the CN test dropped."""
    spans = complete_spans(events)
    selfs = self_us(spans)
    rows: dict[int, dict] = {}
    for e in spans:
        args = e.get("args", {})
        batch = args.get("batch")
        if not batch or e["name"] not in BATCH_COLUMNS:
            continue
        row = rows.setdefault(batch, {"batch": batch, "t_s": e["ts"] / 1e6})
        row["gil"] = row.get("gil", 0.0) + args.get("gil_us", 0.0) / 1e3
        if e["name"] in BATCH_CPU_COLUMNS and "tdur" in e:
            for key, us in (("cpu:", e["tdur"]),
                            ("off:", e["dur"] - e["tdur"])):
                row[key + e["name"]] = row.get(key + e["name"], 0.0) + us / 1e3
        ms = (selfs[e["pid"], e["id"]] if e["name"] == "native.decode_batch"
              else e["dur"]) / 1e3
        row[e["name"]] = row.get(e["name"], 0.0) + ms
        if e["name"] == "decode.native_call":
            row["threads"], row["pad"] = args["threads"], args["pad"]
        if "filtered_cn" in args:  # device.fold under issuerCNFilter
            row["cn_drop"] = row.get("cn_drop", 0) + args["filtered_cn"]
    return [rows[b] for b in sorted(rows)]


def cut_window(events: list[dict], lo: int, hi: int) -> tuple:
    """``(t0_us, t1_us)``: the ends of the spans that cut batches
    ``lo`` and ``hi``."""
    cuts = {e["args"]["batch"]: e["ts"] + e.get("dur", 0.0)
            for e in complete_spans(events)
            if e["name"] == "sink.accumulate" and "batch" in e.get("args", {})}
    if lo not in cuts or hi not in cuts:
        raise ValueError(f"no sink.accumulate span cut batch {lo} or {hi}")
    return cuts[lo], cuts[hi]


def split_by_overlap(events: list[dict], name: str, against,
                     t0_us: float = None, t1_us: float = None) -> dict:
    """``{"covered": stats, "clear": stats}`` over the spans called
    ``name`` (those that start in [t0_us, t1_us], if given): covered
    where spans of the ``against`` names cover at least half of the
    span. ``stats`` is ``{"n", "median_ms", "mean_ms", "share"}``,
    ``share`` the group's mean covered share."""
    spans = complete_spans(events)
    cover: list[list[float]] = []  # the union of the `against` spans
    for e in spans:
        if e["name"] not in against:
            continue
        lo, hi = e["ts"], e["ts"] + e.get("dur", 0.0)
        if cover and lo <= cover[-1][1]:
            cover[-1][1] = max(cover[-1][1], hi)
        else:
            cover.append([lo, hi])
    ends = [hi for _lo, hi in cover]
    groups = {"covered": [], "clear": []}
    for e in spans:
        if e["name"] != name or not e.get("dur"):
            continue
        lo, hi = e["ts"], e["ts"] + e["dur"]
        if (t0_us is not None and lo < t0_us) or (
                t1_us is not None and lo > t1_us):
            continue
        got = 0.0
        for a, b in cover[bisect.bisect_right(ends, lo):]:
            if a >= hi:
                break
            got += min(b, hi) - max(a, lo)
        share = got / e["dur"]
        groups["covered" if share >= 0.5 else "clear"].append(
            (e["dur"] / 1e3, share))
    return {
        key: ({"n": len(rows),
               "median_ms": statistics.median(ms for ms, _s in rows),
               "mean_ms": statistics.fmean(ms for ms, _s in rows),
               "share": statistics.fmean(s for _ms, s in rows)}
              if rows else {"n": 0})
        for key, rows in groups.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", nargs="+",
                    help="Chrome trace-event JSON path(s); several "
                         "with --merge")
    ap.add_argument("--stages", default="",
                    help="comma-separated span names to include "
                         "(default: all)")
    ap.add_argument("--merge", action="store_true",
                    help="stitch per-process traces into one "
                         "skew-corrected timeline")
    ap.add_argument("--skew", default="",
                    help="worker→(wall, mono) clock-pair JSON from the "
                         "coordinator fabric (with --merge)")
    ap.add_argument("--out", default="",
                    help="write the merged trace here (with --merge)")
    ap.add_argument("--batch", type=int, default=0,
                    help="print this ingest batch's lineage")
    ap.add_argument("--batches", action="store_true",
                    help="print one row per ingest batch")
    ap.add_argument("--split", default="",
                    help="span name to split by what overlaps it")
    ap.add_argument("--against", default="",
                    help="comma-separated span names that cover (with "
                         "--split)")
    ap.add_argument("--between", default="",
                    help="LO,HI: only spans that start between the cuts "
                         "of these two batches (with --split)")
    args = ap.parse_args(argv)
    stages = [s for s in args.stages.split(",") if s] or None
    if args.merge:
        merged = merge(args.trace, skew_path=args.skew,
                       out_path=args.out)
        n = sum(1 for e in merged["traceEvents"] if e.get("ph") != "M")
        print(f"merged {merged['otherData']['merged_from']} traces, "
              f"{n} events"
              + (f" -> {args.out}" if args.out else ""))
        events = merged["traceEvents"]
    elif len(args.trace) > 1:
        print("multiple trace files need --merge", file=sys.stderr)
        return 2
    else:
        events = load(args.trace[0])
    if args.split:
        against = {s for s in args.against.split(",") if s}
        window = (cut_window(events, *map(int, args.between.split(",")))
                  if args.between else (None, None))
        split = split_by_overlap(events, args.split, against, *window)
        print(f"{args.split} by {'+'.join(sorted(against))}")
        for key, st in split.items():
            print(f"{key:>8} {st['n']:>6}" + (
                f"  median {st['median_ms']:.3f} ms  mean {st['mean_ms']:.3f}"
                f" ms  covered {100 * st['share']:.1f}%" if st["n"] else ""))
        return 0 if any(st["n"] for st in split.values()) else 1
    if args.batch:
        lines = batch_lineage(events, args.batch)
        print("\n".join(lines) or f"no span carries batch={args.batch}")
        return 0 if lines else 1
    if args.batches:
        split = [k + c for c in BATCH_CPU_COLUMNS for k in ("cpu:", "off:")]
        table = batch_table(events)
        filtered = any("cn_drop" in row for row in table)
        print(f"{'batch':>5} {'t_s':>8} {'thr':>3} {'pad':>5} "
              + " ".join(f"{c.split('.')[1][:11]:>11}" for c in BATCH_COLUMNS)
              + " " + " ".join(f"{c[:4] + c.split('.')[1][:6]:>10}"
                               for c in split) + f" {'gil':>7}"
              + (f" {'cn_drop':>7}" if filtered else ""))
        for row in table:
            print(f"{row['batch']:>5} {row['t_s']:>8.2f} "
                  f"{row.get('threads', 0):>3} {row.get('pad', 0):>5} "
                  + " ".join(f"{row.get(c, 0.0):>11.1f}"
                             for c in BATCH_COLUMNS)
                  + " " + " ".join(f"{row.get(c, 0.0):>10.1f}" for c in split)
                  + f" {row.get('gil', 0.0):>7.2f}"
                  + (f" {row.get('cn_drop', 0):>7}" if filtered else ""))
        return 0
    summary = stage_summary(events, stages=stages)
    wall = summary.pop("_wall_s")
    if not summary:
        print("no complete spans in trace", file=sys.stderr)
        return 1
    print(f"trace wall: {wall:.3f}s over "
          f"{sum(s['count'] for s in summary.values())} spans")
    hdr = (f"{'stage':<28} {'count':>7} {'busy_s':>9} {'cpu_s':>9} "
           f"{'off_s':>9} {'occ':>6} {'max_gap_s':>10}")
    print(hdr)
    print("-" * len(hdr))
    occ_sum = 0.0
    for name in sorted(summary, key=lambda n: -summary[n]["busy_s"]):
        s = summary[name]
        if name == "gil.probe":  # asleep by design: its line is the last
            continue
        occ_sum += s["occupancy"]
        print(f"{name:<28} {s['count']:>7} {s['busy_s']:>9.3f} "
              f"{s['cpu_s']:>9.3f} {s['offcore_s']:>9.3f} "
              f"{s['occupancy']:>6.2f} {s['max_gap_s']:>10.3f}")
    print(f"{'(sum)':<28} {'':>7} {'':>9} {'':>9} {'':>9} {occ_sum:>6.2f}")
    if occ_sum > 1.05:
        print("occupancies sum past 1.0: stages are overlapping")
    families = thread_families(events)
    if families:
        print(f"{'threads':<12} {'roots':>7} {'wall_s':>9} {'cpu_s':>9} "
              f"{'off_s':>9} {'native_s':>9} {'gil_s':>9}")
        for family in sorted(families, key=lambda f: -families[f]["cpu_s"]):
            f = families[family]
            print(f"{family:<12} {f['roots']:>7} {f['wall_s']:>9.3f} "
                  f"{f['cpu_s']:>9.3f} {f['offcore_s']:>9.3f} "
                  f"{f['native_s']:>9.3f} {f['gil_s']:>9.3f}")
    waits = probe_waits(events)
    if waits:
        print(f"gil.probe: {len(waits)} wake-ups, waited mean "
              f"{statistics.fmean(waits) / 1e3:.3f} ms, "
              f"p95 {nearest_rank(waits, 95) / 1e3:.3f} ms, "
              f"max {waits[-1] / 1e3:.3f} ms")
    return 0


if __name__ == "__main__":
    sys.exit(main())
