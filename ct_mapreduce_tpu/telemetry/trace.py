"""Span tracing: a lock-cheap, thread-aware, ring-buffered tracer for
the ingest hot path, exporting Chrome trace-event JSON.

Design constraints (the hot path dispatches ~1M-entry chunks, so spans
are per-CHUNK, but the disabled path must still cost nothing):

- **Disabled = one global read.** ``span()`` reads one module global;
  when no tracer is installed it returns a shared no-op context
  manager — no allocation, no lock, no branch beyond the None check.
- **Enabled = GIL-atomic appends.** Events land in a
  ``collections.deque(maxlen=ring)`` whose ``append`` is atomic under
  the GIL, so concurrent stage threads (decode pool, submit, drain)
  never contend on a lock in ``__exit__``. The ring bound (default
  2^16 events, ``CTMR_TRACE_RING``) means a week-long ``runForever``
  deployment keeps the LAST window of activity instead of growing
  without limit — exactly what the flight recorder wants.
- **Chrome trace-event format.** Export is the Trace Event Format's
  JSON-object form (``{"traceEvents": [...]}``): complete spans
  (``ph="X"`` with ``ts``/``dur`` in microseconds), instant events
  (``ph="i"``), and thread-name metadata (``ph="M"``) — loadable in
  Perfetto / ``chrome://tracing`` as-is, and summarizable offline by
  ``tools/traceview.py``.
- **Optional XLA alignment.** ``jax_annotations=True`` (or
  ``CTMR_TRACE_JAX=1``) additionally enters a
  ``jax.profiler.TraceAnnotation`` per span, so when a jax profiler
  trace (``profileDir``) runs alongside, the host-side stage spans
  line up with the device timeline in the same viewer. The span's
  scalar arguments ride along as the event's stats; the event's name
  stays the bare span name.
- **Cause and identity.** Every event carries an ``id`` and the
  ``parent`` id of the span that enclosed it on the same thread (0 at
  a thread's root), from a thread-local stack: a span's self time is
  its ``dur`` less its children's by ``parent``, whatever other
  threads record under the same name. The identity arguments
  (``batch``, ``reason``) flow down that stack — a span that does not
  set one takes its parent's — so every span one ingest batch causes
  carries the same ``batch`` without each callee being handed it;
  work that continues on another thread passes ``batch=`` itself.
  :meth:`_Span.set` adds arguments known only once the work is done.
  A span that is no ``with`` block (a connection served in turns by
  the query front's loop) is handed over whole, with the ``parent`` its
  owner kept: :meth:`SpanTracer.record_span`.
- **Drops are counted.** The ring forgets its oldest events;
  :meth:`SpanTracer.dropped` (and ``otherData.dropped`` in an export)
  says how many, so a reader can refuse a window it did not see whole.
- **Working is told from waiting.** A span reads its thread's CPU clock
  (``time.thread_time_ns``) beside the wall clock, and its event
  carries the format's own thread-clock fields ``tts`` / ``tdur`` (µs,
  shown as CPU duration by Perfetto). ``dur - tdur`` is what the thread
  spent off a core under the span: blocked on a socket, a lock, a
  queue, the device, or waiting for the GIL; for a span that blocks on
  nothing it is the GIL wait. A span around a native call that
  releases the GIL also says how long the library ran (``native_us``)
  and how long the thread then took to get the GIL back (``gil_us``:
  ``native.note_return``).
- **The GIL is probed.** While the module-level tracer is on, the
  thread ``ctmr-gil-probe`` sleeps 10 ms in the native library, which
  stamps the clock as it wakes, and records a span ``gil.probe`` whose
  ``wait_us`` is how much later Python ran again: what a thread pays
  for the GIL each time a ``recv`` or a native call returns. No probe
  where the native library is absent or does not stamp, nor for a
  :class:`SpanTracer` built directly.

Enabling: the ``CTMR_TRACE=<path>`` environment variable (read at
import, so every entry point — ct-fetch, tests — gets it for
free) or the ``tracePath`` config directive / an explicit
:func:`enable` call. When a path is set, the ring is exported there at
interpreter exit; callers may also :func:`export` eagerly.

Cross-process correlation (round 23): spans can carry a
``trace_id``/``parent_id`` pair. A request thread establishes the pair
with :func:`trace_context` (typically parsed from a W3C-style
``traceparent`` header minted by :func:`mint_traceparent`) and every
span recorded on that thread while the context is active is tagged.
:func:`set_process_attrs` stamps process-wide identity (fleet
``worker``, leader ``epoch``) onto every event, and exports carry a
``mono_t0`` anchor on ``time.monotonic()`` — the clock the coordinator
fabric's (wall, monotonic) pairs reference — so
``tools/traceview.py --merge`` can place per-process rings on one
skew-corrected timeline.
"""

from __future__ import annotations

import atexit
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Optional

DEFAULT_RING = 1 << 16  # events; ~25 MB worst case, bounds long runs

# -- cross-process correlation state ------------------------------------
# Process-wide attrs (fleet worker id, leader epoch) merged into every
# recorded event; span-local args win on key collisions.
_proc_attrs: dict = {}
# Per-thread trace context: (trace_id, parent_id) or absent; and the
# thread's stack of live spans (``stack``).
_ctx = threading.local()
# Identity arguments: a span that does not set one takes its parent's.
_INHERITED = ("batch", "reason")
# What a TraceAnnotation can carry as a stat without breaking the
# ``name#k=v,...#`` encoding the profiler parses.
_STAT_BREAKERS = frozenset("#,=")
_tracer_serials = itertools.count(1)


def set_process_attrs(**attrs) -> None:
    """Stamp (or update) process-wide attrs onto every future event.
    ``None`` values delete the key."""
    for key, val in attrs.items():
        if val is None:
            _proc_attrs.pop(key, None)
        else:
            _proc_attrs[key] = val


def get_process_attrs() -> dict:
    return dict(_proc_attrs)


def set_trace_context(trace_id: str,
                      parent_id: Optional[str] = None) -> None:
    _ctx.ids = (trace_id, parent_id)


def clear_trace_context() -> None:
    _ctx.ids = None


def get_trace_context() -> Optional[tuple]:
    """The calling thread's (trace_id, parent_id), or None."""
    return getattr(_ctx, "ids", None)


class trace_context:
    """Context manager scoping a (trace_id, parent_id) pair to the
    calling thread; restores the previous context on exit. A falsy
    ``trace_id`` makes it a no-op (so callers can pass a parse result
    straight through)."""

    __slots__ = ("_ids", "_prev")

    def __init__(self, trace_id: Optional[str],
                 parent_id: Optional[str] = None):
        self._ids = (trace_id, parent_id) if trace_id else None

    def __enter__(self):
        self._prev = getattr(_ctx, "ids", None)
        if self._ids is not None:
            _ctx.ids = self._ids
        return self

    def __exit__(self, *exc):
        _ctx.ids = self._prev
        return False


# -- W3C-traceparent-style header helpers -------------------------------
# Wire shape: "00-<32 hex trace_id>-<16 hex span_id>-01" (version and
# sampled flag fixed; only the two ids are meaningful here).

TRACEPARENT_HEADER = "traceparent"


def new_trace_id() -> str:
    return os.urandom(16).hex()


def new_span_id() -> str:
    return os.urandom(8).hex()


def mint_traceparent() -> tuple[str, str, str]:
    """(header_value, trace_id, span_id) for a new client-side root."""
    trace_id, span_id = new_trace_id(), new_span_id()
    return f"00-{trace_id}-{span_id}-01", trace_id, span_id


def format_traceparent(trace_id: str, span_id: str) -> str:
    return f"00-{trace_id}-{span_id}-01"


def parse_traceparent(header: Optional[str]) -> Optional[tuple]:
    """(trace_id, span_id) from a traceparent header, or None on any
    malformation — propagation must never reject a request."""
    if not header:
        return None
    parts = header.strip().split("-")
    if len(parts) != 4:
        return None
    version, trace_id, span_id, _flags = parts
    if len(version) != 2 or len(trace_id) != 32 or len(span_id) != 16:
        return None
    try:
        int(version, 16), int(trace_id, 16), int(span_id, 16)
    except ValueError:
        return None
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return trace_id, span_id


class _NullSpan:
    """Shared no-op context manager: the disabled-tracing fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):
        return self


_NULL_SPAN = _NullSpan()


class _Span:
    """One live span; records a complete ("X") event on exit."""

    __slots__ = ("_tracer", "_name", "_cat", "_args", "_t0", "_c0", "_ann",
                 "id", "parent")

    def __init__(self, tracer: "SpanTracer", name: str, cat: str, args):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._args = args
        self._ann = None

    def set(self, **args):
        """Add arguments known only once the work is under way (bytes
        read, attempts made, the batch a cut produced). They reach the
        ring, and the spans opened under this one afterwards; the
        profiler's mirror was given its stats at entry."""
        self._args.update(args)
        return self

    def __enter__(self):
        stack = getattr(_ctx, "stack", None)
        if stack is None:
            stack = _ctx.stack = []
        self.id = next(self._tracer._ids)
        if stack:
            top = stack[-1]
            self.parent = top.id
            for key in _INHERITED:
                if key in top._args and key not in self._args:
                    self._args[key] = top._args[key]
        else:
            self.parent = 0
        stack.append(self)
        if self._tracer.jax_annotations:
            try:
                from jax.profiler import TraceAnnotation

                self._ann = TraceAnnotation(self._name, **{
                    k: v for k, v in self._args.items()
                    if isinstance(v, (int, float))
                    or (isinstance(v, str) and _STAT_BREAKERS.isdisjoint(v))})
                self._ann.__enter__()
            except Exception:
                self._ann = None  # tracing must never break the pipeline
        # The CPU clock is read inside the wall clock's readings, at
        # both ends, so tdur never exceeds dur.
        self._t0 = time.perf_counter_ns()
        self._c0 = time.thread_time_ns()
        return self

    def __exit__(self, *exc):
        c1 = time.thread_time_ns()
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            try:
                self._ann.__exit__(*exc)
            except Exception:
                pass
        stack = _ctx.stack
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:  # exited out of order: keep the rest sound
            stack.remove(self)
        self._tracer._complete(self._name, self._cat, self._t0, t1,
                               self._c0, c1, self._args, self.id,
                               self.parent)
        return False


class SpanTracer:
    def __init__(self, path: Optional[str] = None,
                 ring_size: int = DEFAULT_RING,
                 jax_annotations: bool = False):
        self.path = path or None
        self.ring_size = max(16, int(ring_size))
        self.jax_annotations = bool(jax_annotations)
        # deque.append is GIL-atomic: the hot path never takes a lock.
        self._events: deque = deque(maxlen=self.ring_size)
        self._ids = itertools.count(1)  # next() is GIL-atomic
        # Events appended, per thread: each entry has one writer, so
        # the sum is exact with no lock on the hot path.
        self._appended: dict[int, int] = {}
        self._t0_ns = time.perf_counter_ns()
        # Anchors recorded back to back: wall-clock (place the ring in
        # real time) and CLOCK_MONOTONIC (the clock the fleet fabric's
        # (wall, monotonic) pairs reference — the skew-correction base
        # for tools/traceview.py --merge).
        self.wall_t0 = time.time()
        self.mono_t0 = time.monotonic()
        self._pid = os.getpid()
        self._threads_lock = threading.Lock()
        # (ident, name) of every thread that recorded here. The OS
        # hands a dead thread's ident to the next one, so names are
        # kept per thread (a thread-local mark), not per ident.
        self._thread_names: set[tuple[int, str]] = set()
        self._serial = next(_tracer_serials)

    # -- recording -------------------------------------------------------
    def now_us(self) -> float:
        """Current timestamp on the tracer's own clock (µs since
        construction) — for callers windowing :meth:`events`."""
        return (time.perf_counter_ns() - self._t0_ns) / 1e3

    def _tid(self) -> int:
        tid = threading.get_ident()
        if getattr(_ctx, "named_in", 0) != self._serial:
            _ctx.named_in = self._serial
            with self._threads_lock:
                self._thread_names.add(
                    (tid, threading.current_thread().name))
                self._appended.setdefault(tid, 0)
        return tid

    def _append(self, ev: dict) -> None:
        self._appended[ev["tid"]] += 1
        self._events.append(ev)

    def _tagged_args(self, args) -> Optional[dict]:
        """Span args merged with the process attrs and the calling
        thread's trace context (span-local args win)."""
        ids = getattr(_ctx, "ids", None)
        if not _proc_attrs and ids is None:
            return dict(args) if args else None
        merged = dict(_proc_attrs)
        if ids is not None:
            merged["trace_id"] = ids[0]
            if ids[1]:
                merged["parent_id"] = ids[1]
        if args:
            merged.update(args)
        return merged

    def _complete(self, name: str, cat: str, t0_ns: int, t1_ns: int,
                  c0_ns: int, c1_ns: int, args, span_id: int,
                  parent: int) -> None:
        ev = {
            "name": name,
            "ph": "X",
            "ts": (t0_ns - self._t0_ns) / 1e3,
            "dur": max(t1_ns - t0_ns, 0) / 1e3,
            # The thread's own CPU clock, from the thread's start.
            "tts": c0_ns / 1e3,
            "tdur": max(c1_ns - c0_ns, 0) / 1e3,
            "pid": self._pid,
            "tid": self._tid(),
            "id": span_id,
            "parent": parent,
        }
        if cat:
            ev["cat"] = cat
        tagged = self._tagged_args(args)
        if tagged:
            ev["args"] = tagged
        self._append(ev)

    def span(self, name: str, cat: str = "", **args) -> _Span:
        return _Span(self, name, cat, args)

    def next_id(self) -> int:
        """The ``id`` of a span that :meth:`record_span` will record
        later: its children name it as ``parent`` before it ends."""
        return next(self._ids)

    def record_span(self, name: str, cat: str, t0_ns: int, t1_ns: int,
                    tts_ns: int = 0, tdur_ns: int = 0, span_id: int = 0,
                    parent: int = 0, **args) -> None:
        """A complete span whose life was not one ``with`` block on one
        thread's stack: a connection that a loop serves in turns, among
        the turns of others. The caller read the clocks (``t0_ns`` /
        ``t1_ns`` on ``time.perf_counter_ns``; ``tts_ns`` the recording
        thread's CPU clock at the first turn, ``tdur_ns`` the CPU of the
        turns, summed) and names the ``parent`` itself. Recorded by the
        calling thread, with its trace context."""
        self._complete(name, cat, t0_ns, t1_ns, tts_ns, tts_ns + tdur_ns,
                       args, span_id or next(self._ids), parent)

    def instant(self, name: str, cat: str = "", **args) -> None:
        stack = getattr(_ctx, "stack", None)
        ev = {
            "name": name,
            "ph": "i",
            "s": "t",  # thread-scoped instant
            "ts": self.now_us(),
            "tts": time.thread_time_ns() / 1e3,
            "pid": self._pid,
            "tid": self._tid(),
            "id": next(self._ids),
            "parent": stack[-1].id if stack else 0,
        }
        if cat:
            ev["cat"] = cat
        tagged = self._tagged_args(args)
        if tagged:
            ev["args"] = tagged
        self._append(ev)

    # -- reading / export ------------------------------------------------
    def events(self) -> list[dict]:
        """Ring contents plus thread-name metadata, oldest first."""
        with self._threads_lock:
            meta = [
                {"name": "thread_name", "ph": "M", "pid": self._pid,
                 "tid": tid, "args": {"name": tname}}
                for tid, tname in sorted(self._thread_names)
            ]
        return meta + list(self._events)

    def dropped(self) -> int:
        """Events the ring has forgotten since construction (or the
        last :meth:`clear`). They are always the oldest: a window that
        starts after the oldest retained event ended was seen whole."""
        with self._threads_lock:
            appended = sum(self._appended.values())
        return max(0, appended - len(self._events))

    def clear(self) -> None:
        """Empty the ring and its drop count. For a reader between
        windows: an event appended while this runs may be miscounted."""
        with self._threads_lock:
            self._events.clear()
            for tid in self._appended:
                self._appended[tid] = 0

    def export(self, path: Optional[str] = None) -> Optional[str]:
        """Write the Chrome trace JSON; returns the path (None if no
        path is known). Never raises — an unwritable trace file must
        not take down the run it describes."""
        path = path or self.path
        if not path:
            return None
        doc = {
            "traceEvents": self.events(),
            "displayTimeUnit": "ms",
            "otherData": {"wall_t0": self.wall_t0,
                          "mono_t0": self.mono_t0,
                          "pid": self._pid,
                          "process_attrs": get_process_attrs(),
                          "ring_size": self.ring_size,
                          "dropped": self.dropped()},
        }
        try:
            with open(path, "w") as fh:
                json.dump(doc, fh)
        except OSError:
            return None
        return path


# -- the GIL probe ------------------------------------------------------

PROBE_PERIOD_NS = 10_000_000  # 100 wake-ups a second


class _GilProbe:
    """The thread ``ctmr-gil-probe`` of one tracer: it sleeps in the
    native library, GIL released, which stamps CLOCK_MONOTONIC as it
    wakes (``ctmr_sleep_stamp``), and records a span ``gil.probe`` whose
    ``wait_us`` is this thread's first reading of the same clock less
    that stamp: how long a thread that has just been woken waits for
    the GIL, as the downloader does after every ``recv`` and the store
    thread after every native call. It ends when told to, or when the
    module's tracer is no longer the one it records into."""

    def __init__(self, tracer: SpanTracer, sleep_stamp):
        self.tracer = tracer
        self._sleep_stamp = sleep_stamp
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="ctmr-gil-probe", daemon=True)
        self._thread.start()

    def _run(self) -> None:
        tracer, sleep_stamp = self.tracer, self._sleep_stamp
        while not self._stop.is_set() and _tracer is tracer:
            with tracer.span("gil.probe", cat="gil") as sp:
                woke = sleep_stamp(PROBE_PERIOD_NS)
                back = time.monotonic_ns()
                sp.set(wait_us=max(back - woke, 0) / 1e3)

    def alive(self) -> bool:
        return self._thread.is_alive()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join()


def _start_probe(tracer: SpanTracer) -> Optional[_GilProbe]:
    try:
        from ct_mapreduce_tpu import native

        lib = native.load()
    except Exception:
        return None  # tracing must never break the pipeline
    if lib is None or not getattr(lib, "has_stamp", False):
        return None
    return _GilProbe(tracer, lib.ctmr_sleep_stamp)


# -- module-level tracer (the hot path reads one global) ----------------

_tracer: Optional[SpanTracer] = None
_probe: Optional[_GilProbe] = None
_atexit_registered = False


def enabled() -> bool:
    return _tracer is not None


def get_tracer() -> Optional[SpanTracer]:
    return _tracer


def enable(path: Optional[str] = None, ring_size: Optional[int] = None,
           jax_annotations: Optional[bool] = None) -> SpanTracer:
    """Install the global tracer (idempotent: re-enabling with a path
    updates the export path of the live tracer rather than dropping
    its ring)."""
    global _tracer, _probe, _atexit_registered
    if ring_size is None:
        ring_size = int(os.environ.get("CTMR_TRACE_RING", DEFAULT_RING))
    if jax_annotations is None:
        jax_annotations = os.environ.get("CTMR_TRACE_JAX", "0") == "1"
    if _tracer is None:
        _tracer = SpanTracer(path=path, ring_size=ring_size,
                             jax_annotations=jax_annotations)
    else:
        if path:
            _tracer.path = path
        if jax_annotations:
            _tracer.jax_annotations = True
    if _probe is None or _probe.tracer is not _tracer or not _probe.alive():
        _probe = _start_probe(_tracer)
    if not _atexit_registered:
        atexit.register(_export_at_exit)
        _atexit_registered = True
    return _tracer


def disable() -> None:
    """Take the global tracer away and end its probe thread."""
    global _tracer, _probe
    _tracer = None
    if _probe is not None:
        _probe.stop()
        _probe = None


def _export_at_exit() -> None:
    t = _tracer
    if t is not None and t.path:
        t.export()


def span(name: str, cat: str = "", **args):
    """A span context manager; the shared no-op when tracing is off."""
    t = _tracer
    if t is None:
        return _NULL_SPAN
    return t.span(name, cat, **args)


def annotate(**args) -> None:
    """Add arguments to the innermost span open on the calling thread
    (:meth:`_Span.set` from below it: the callee learns what the
    caller's span should say). Nothing where no span is open."""
    stack = getattr(_ctx, "stack", None)
    if stack:
        stack[-1].set(**args)


def annotate_sum(**args) -> None:
    """:func:`annotate` for numbers that add up over a span's life
    (the native calls under it): each is added to what the innermost
    span already says under that name."""
    stack = getattr(_ctx, "stack", None)
    if stack:
        have = stack[-1]._args
        for key, val in args.items():
            have[key] = have.get(key, 0) + val


def instant(name: str, cat: str = "", **args) -> None:
    t = _tracer
    if t is not None:
        t.instant(name, cat, **args)


def snapshot_events() -> list[dict]:
    """Current ring contents (for the flight recorder); [] when off."""
    t = _tracer
    return t.events() if t is not None else []


def export(path: Optional[str] = None) -> Optional[str]:
    t = _tracer
    return t.export(path) if t is not None else None


# CTMR_TRACE=<path> enables tracing for any entry point at import time.
_env_path = os.environ.get("CTMR_TRACE", "")
if _env_path:
    enable(_env_path)
