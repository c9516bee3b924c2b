"""The traced run: JAX's profiler over the window, the program's spans on
the same clock, and the reduction from the ``.xplane.pb`` to numbers.

Stage 1 (:func:`load_xplane`) flattens the profile into plain lists;
stage 2 (:func:`reduce_trace`) is arithmetic on those lists and is
checked against the recorded trace in ``tests/data``.
"""

from __future__ import annotations

import bisect
import glob
import os
import time

import numpy as np

# Host work that can explain why the device waited, besides the
# program's own spans (which reach the profile as TraceMe events):
# whether the log server had a page in flight, from its stamps.
PAGE_IN_FLIGHT = "loadgen.page_in_flight"
BETWEEN_PAGES = "fetch.between_pages"
ANCHOR = "bench.anchor"
# The program's span names (telemetry/trace.py callers), by family.
SPAN_PREFIXES = ("ingest.", "device.", "native.", "mesh.", "fetch.", "sink.",
                 "decode.", "fold.", "ckpt.")
# Device gaps shorter than this lie inside one program (between its
# ops) and are not worth a name each.
MIN_GAP_S = 200e-6


def enable_program_spans() -> None:
    """Turn on the program's span tracer, with each span mirrored into
    the profiler's host plane so that both share one clock."""
    from ct_mapreduce_tpu.telemetry import trace

    trace.enable(ring_size=1 << 20, jax_annotations=True)


class WindowTrace:
    def __init__(self, directory: str):
        self.directory = directory
        self.t_start = self.t_stop = self.anchor = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self.t_start = self.anchor = time.monotonic()
        with jax.profiler.TraceAnnotation(ANCHOR):
            pass

    def stop(self) -> None:
        import jax

        self.t_stop = time.monotonic()
        jax.profiler.stop_trace()

    def xplane(self) -> str:
        found = glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb"))
        if len(found) != 1:
            raise RuntimeError(f"want one .xplane.pb, found {found}")
        return found[0]


def load_xplane(path: str) -> dict:
    """Per device plane the ``XLA Ops`` and ``XLA Modules`` events (an
    op's name cut to what stands before `` = `` in its HLO text), and
    the host plane's named events, as ``(name, start_s, seconds)`` on
    the profile's own clock."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = {"devices": {}, "host": [], "lines": {}}
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        out["lines"][plane.name] = sorted(lines)
        if plane.name.startswith("/device:TPU:"):
            def events(name):
                line = lines.get(name)
                return [] if line is None else [
                    (short(e.name), e.start_ns / 1e9, e.duration_ns / 1e9)
                    for e in line.events]
            out["devices"][plane.name] = {
                "ops": events("XLA Ops"), "modules": events("XLA Modules")}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["host"] += [
                    (e.name, e.start_ns / 1e9, e.duration_ns / 1e9)
                    for e in line.events
                    if e.name == ANCHOR or e.name.startswith(SPAN_PREFIXES)]
    return out


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return [(lo, hi) for lo, hi in merged]


class Covered:
    """A union of intervals that answers "how many seconds of
    ``[lo, hi]`` do you cover" in logarithmic time."""

    def __init__(self, intervals: list[tuple[float, float]]):
        merged = union(intervals)
        self.lo = np.array([a for a, _ in merged], np.float64)
        self.hi = np.array([b for _, b in merged], np.float64)
        self.cum = np.concatenate(([0.0], np.cumsum(self.hi - self.lo)))

    def upto(self, t: float) -> float:
        k = int(np.searchsorted(self.lo, t, side="right"))
        if k == 0:
            return 0.0
        return float(self.cum[k - 1] + min(t, self.hi[k - 1]) - self.lo[k - 1])

    def overlap(self, lo: float, hi: float) -> float:
        return self.upto(hi) - self.upto(lo)


# Spans that wrap other spans of the program: where an inner one is in
# the profile it names its part of a gap, and the outer one keeps what
# no inner one covers (its self time).
WRAPS = {"ingest.decode": ("native.decode_batch",),
         "native.decode_batch": ("decode.concat_b64", "decode.native_call",
                                 "decode.issuer_groups", "decode.pack"),
         "device.readback": ("device.fold",),
         "device.fold": ("fold.wait_device",),
         "ingest.submit_locked": ("ingest.submit",),
         "fetch.page": ("fetch.get_entries", "fetch.parse_json",
                        "fetch.enqueue"),
         "fetch.save_cursor": ("ckpt.wait_outstanding",),
         "ckpt.save": ("ckpt.d2h", "ckpt.write", "ckpt.seal")}


def less(outer: list[tuple[float, float]],
         inner: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """The parts of ``outer``'s intervals that no interval of ``inner``
    covers."""
    cuts = union(inner)
    ends = [b for _a, b in cuts]
    out = []
    for lo, hi in outer:
        for k in range(bisect.bisect_right(ends, lo), len(cuts)):
            a, b = cuts[k]
            if a >= hi:
                break
            if a > lo:
                out.append((lo, a))
            lo = b
        if lo < hi:
            out.append((lo, hi))
    return out


def short(op: str) -> str:
    """``%while.74 = (s32[] ...`` -> ``while.74``."""
    return op.split(" = ")[0].lstrip("%")


def clip(events, lo: float, hi: float):
    return [(n, max(s, lo), min(s + d, hi) - max(s, lo))
            for n, s, d in events if s < hi and s + d > lo]


def reduce_trace(xp: dict, lo: float, hi: float,
                 host_spans: dict[str, list[tuple[float, float]]]) -> dict:
    """Over ``[lo, hi]`` on the profile's clock: busy seconds (union of
    op intervals, averaged over the device planes), seconds per op name
    and per module name, and the device's idle gaps named by what the
    host was doing in them.

    ``host_spans`` maps a name to intervals on the profile's clock; a
    gap's seconds are shared among the names that overlap it, in
    proportion, and what no name covers goes to ``no_span``."""
    busy = []
    ops: dict[str, float] = {}
    modules: dict[str, float] = {}
    gaps: dict[str, float] = {}
    op_calls: dict[str, int] = {}
    module_calls: dict[str, int] = {}
    host_spans = {k: less(v, [iv for inner in WRAPS.get(k, ())
                              for iv in host_spans.get(inner, ())])
                  for k, v in host_spans.items()}
    named = {name: Covered(ivals) for name, ivals in host_spans.items()}
    covered = Covered([iv for ivals in host_spans.values() for iv in ivals])
    first_plane = True
    for _name, plane in sorted(xp["devices"].items()):
        plane_ops = clip(plane["ops"], lo, hi)
        spans = union([(s, s + d) for _n, s, d in plane_ops])
        busy.append(sum(b - a for a, b in spans))
        for n, _s, d in plane_ops:
            ops[n] = ops.get(n, 0.0) + d
            op_calls[n] = op_calls.get(n, 0) + 1
        for n, _s, d in clip(plane["modules"], lo, hi):
            modules[n] = modules.get(n, 0.0) + d
            module_calls[n] = module_calls.get(n, 0) + 1
        if not first_plane:
            continue
        first_plane = False
        edges = [(lo, lo)] + spans + [(hi, hi)]
        for (_a, g0), (g1, _b) in zip(edges, edges[1:]):
            if g1 - g0 <= 0.0:
                continue
            if g1 - g0 < MIN_GAP_S:
                gaps["within_program"] = gaps.get("within_program", 0.0) + g1 - g0
                continue
            share = {name: c.overlap(g0, g1) for name, c in named.items()}
            total = sum(share.values())
            cov = covered.overlap(g0, g1)
            gaps["no_span"] = gaps.get("no_span", 0.0) + (g1 - g0) - cov
            for name, s in share.items():
                if s > 0.0:
                    gaps[name] = gaps.get(name, 0.0) + cov * s / total
    n = max(1, len(busy))
    top = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:10]  # noqa: E731
    return {"busy_s": sum(busy) / n, "window_s": hi - lo,
            "ops": ops, "modules": modules,
            "op_calls": op_calls, "module_calls": module_calls,
            "device_ops": [[k, v] for k, v in top(ops)],
            "idle_gaps": [[k, v] for k, v in top(gaps)]}


def host_intervals(xp: dict, anchor_mono: float, pages: list[tuple]):
    """The program's spans from the host plane, the log server's page
    stamps moved onto the profile's clock through the anchor, and the
    shift from ``time.monotonic()`` to that clock."""
    out: dict[str, list[tuple[float, float]]] = {}
    anchor = next((s for n, s, _d in xp["host"] if n == ANCHOR), None)
    if anchor is None:
        raise RuntimeError("the profile has no anchor event")
    shift = anchor - anchor_mono
    for name, start, dur in xp["host"]:
        if name != ANCHOR:
            out.setdefault(name, []).append((start, start + dur))
    if pages:
        pages = sorted(pages, key=lambda p: p[3])
        out[PAGE_IN_FLIGHT] = [(p[3] + shift, p[5] + shift) for p in pages]
        out[BETWEEN_PAGES] = [(a[5] + shift, b[3] + shift)
                              for a, b in zip(pages, pages[1:])]
    return out, shift
