"""The declared lock hierarchy — the package's lock-order contract as
data.

Every ``threading.Lock``/``RLock`` in ``ct_mapreduce_tpu`` is declared
here with a **rank** in the global partial order: a thread holding a
lock of rank R may only acquire locks of rank **strictly greater**
than R. Ranks are spaced so new locks slot in without renumbering.
Locks that can never be held together still get distinct ranks — the
rank then documents where they'd sit if composition ever nests them.

The chain the ISSUE names (``agg/aggregator.py:482-494``,
``ingest/sync.py:185-189``) is the trunk::

    serve.pool_refresh/pool           (12-14)  snapshot capture wrappers
        ingest.dispatch               (20)     ONE device stream
            agg.save                  (24)     checkpoint writer
                agg.pending           (30)     per-pending claim
                    agg.fold          (40)     host-state fold-ins
                        agg.table     (44)     table-swap guard
                            ingest.pem(48)     PEM tree writes
                                storage.*     (52-62)  backend/caches
                                    ...innermost: telemetry (90-94)

Consumed by BOTH halves of the round-16 tooling: the static
``lock-order`` rule (flags ``with``-nests against the order and any
lock attribute not declared here) and the runtime witness
(``analysis/witness.py`` maps creation sites to these names via
:func:`build_site_table` and checks real acquisition chains).

jax-free on purpose (see package docstring).
"""

from __future__ import annotations

import ast
import fnmatch
import pathlib
from dataclasses import dataclass
from typing import Optional


@dataclass(frozen=True)
class LockDecl:
    name: str  # hierarchy name, e.g. "agg.fold"
    path: str  # repo-relative module path (fnmatch pattern)
    cls: Optional[str]  # enclosing class; None = module level
    attr: str  # attribute / module-variable name
    rank: Optional[int]  # position in the partial order; None = leaf
    # with no ordering constraints (witness still graphs it)
    doc: str = ""


# NOTE: several distinct per-item locks share one name on purpose
# (the three Pending* classes): they are the same hierarchy node, and
# same-name nesting is exempt from order checks (distinct instances
# of one role, e.g. two aggregators' fold locks in a merge, are not
# statically distinguishable).
LOCKS: tuple[LockDecl, ...] = (
    # -- serve plane (outermost: may wrap a full aggregate capture) -----
    LockDecl("serve.pool_refresh", "ct_mapreduce_tpu/serve/snapshot.py",
             "ReplicaPool", "_refresh_lock", 12,
             "one capture in flight; held across capture + pin"),
    LockDecl("serve.pool", "ct_mapreduce_tpu/serve/snapshot.py",
             "ReplicaPool", "_lock", 14, "replica list + epoch counter"),
    # -- ingest device stream -------------------------------------------
    LockDecl("ingest.pending_buf", "ct_mapreduce_tpu/ingest/sync.py",
             "AggregatorSink", "_lock", 16,
             "pending entry/raw buffers; released before dispatch"),
    LockDecl("ingest.dispatch", "ct_mapreduce_tpu/ingest/sync.py",
             "AggregatorSink", "_dispatch_lock", 20,
             "serializes the donated device stream (ONE stream per "
             "table, however many store workers feed it)"),
    LockDecl("ops.ecdsa_tables", "ct_mapreduce_tpu/ops/ecdsa.py",
             None, "_TABLE_LOCK", 22,
             "precompute-table build/LRU caches; the verify lane "
             "builds under ingest.dispatch"),
    LockDecl("agg.save", "ct_mapreduce_tpu/agg/aggregator.py",
             "TpuAggregator", "_save_lock", 24,
             "whole-checkpoint writes (fleet cadence vs run's own save)"),
    LockDecl("agg.emit", "ct_mapreduce_tpu/agg/aggregator.py",
             "TpuAggregator", "_emit_lock", 26,
             "filter emission after a save (outside agg.save since "
             "round 22 — a multi-second build must not block the "
             "fleet save fan-out); acquires agg.fold inside"),
    LockDecl("agg.pending", "ct_mapreduce_tpu/agg/aggregator.py",
             "PendingIngest", "_lock", 30,
             "claim-before-fold; acquires agg.fold inside"),
    LockDecl("agg.pending", "ct_mapreduce_tpu/agg/aggregator.py",
             "PendingPreparsed", "_lock", 30, "same role, preparsed lane"),
    LockDecl("verify.keys", "ct_mapreduce_tpu/verify/lane.py",
             "LogKeyRegistry", "_lock", 36, "trust-anchor map"),
    LockDecl("agg.fold", "ct_mapreduce_tpu/agg/aggregator.py",
             "TpuAggregator", "_fold_lock", 40,
             "host-state fold-ins; documented order: fold, then table"),
    LockDecl("agg.table", "ct_mapreduce_tpu/agg/aggregator.py",
             "TpuAggregator", "_table_lock", 44,
             "table swaps vs concurrent reads (RLock: grow re-enters)"),
    LockDecl("ingest.pem", "ct_mapreduce_tpu/ingest/sync.py",
             "AggregatorSink", "_pem_lock", 48,
             "durable PEM tree writes (store workers vs per-entry path)"),
    # -- storage backends (inside the ingest chain via _store_pems) ------
    LockDecl("storage.certdb_meta", "ct_mapreduce_tpu/storage/certdb.py",
             "FilesystemDatabase", "_meta_lock", 52,
             "issuer-metadata map (RLock)"),
    LockDecl("storage.known_lru", "ct_mapreduce_tpu/storage/certdb.py",
             "_LRU", "_lock", 54,
             "known-certs LRU; factory runs cache loads inside"),
    LockDecl("storage.issuer_meta",
             "ct_mapreduce_tpu/storage/issuermetadata.py",
             "IssuerMetadata", "_lock", 56, "per-issuer CRL/DN sets"),
    LockDecl("storage.redis", "ct_mapreduce_tpu/storage/rediscache.py",
             "RespClient", "_lock", 60, "one RESP2 connection"),
    LockDecl("storage.mock", "ct_mapreduce_tpu/storage/mockcache.py",
             "MockRemoteCache", "_lock", 62, "in-process cache fake"),
    LockDecl("agg.registry", "ct_mapreduce_tpu/agg/aggregator.py",
             "IssuerRegistry", "_lock", 64,
             "issuer indexing; called under agg.fold by merge paths"),
    # -- engine / fleet bookkeeping (leaf-ish, metrics inside) -----------
    LockDecl("ingest.engine_update", "ct_mapreduce_tpu/ingest/sync.py",
             "LogSyncEngine", "_last_update_lock", 70,
             "health-surface progress map"),
    LockDecl("ingest.engine_active", "ct_mapreduce_tpu/ingest/sync.py",
             "LogSyncEngine", "_active_lock", 72,
             "live LogWorker registry (checkpoint fan-out)"),
    LockDecl("fleet.service", "ct_mapreduce_tpu/ingest/fleet.py",
             "FleetService", "_lock", 74,
             "claims/partition/errors; released before fabric calls"),
    LockDecl("serve.cache", "ct_mapreduce_tpu/serve/cache.py",
             "HotSerialCache", "_lock", 82, "hot-serial LRU"),
    LockDecl("distrib.store", "ct_mapreduce_tpu/distrib/publish.py",
             "FilterDistributor", "_lock", 83,
             "published epochs + delta chain + compression cache "
             "(checkpoint publishes vs HTTP reads; only telemetry "
             "nests inside)"),
    LockDecl("native.build", "ct_mapreduce_tpu/native/__init__.py",
             None, "_LOCK", 84, "one native build at a time"),
    LockDecl("utils.miniredis", "ct_mapreduce_tpu/utils/miniredis.py",
             "MiniRedis", "_lock", 86,
             "server-side store (own accept threads; never nests "
             "client-side locks)"),
    # -- telemetry (innermost: emitted from under every other lock) ------
    LockDecl("telemetry.flight", "ct_mapreduce_tpu/telemetry/flight.py",
             "FlightRecorder", "_lock", 90, "dump serialization"),
    LockDecl("telemetry.metrics", "ct_mapreduce_tpu/telemetry/metrics.py",
             "InMemSink", "_lock", 92, "sink state; every emit"),
    LockDecl("telemetry.trace", "ct_mapreduce_tpu/telemetry/trace.py",
             "SpanTracer", "_threads_lock", 94, "thread-name registry"),
)

RANKS: dict[str, Optional[int]] = {}
for _d in LOCKS:
    # Same-name redeclarations must agree on rank (one hierarchy node).
    if _d.name in RANKS and RANKS[_d.name] != _d.rank:
        raise ValueError(f"lockspec rank conflict for {_d.name}")
    RANKS[_d.name] = _d.rank


def decl_for(relpath: str, cls: Optional[str],
             attr: str) -> Optional[LockDecl]:
    """Exact declaration for a lock defined at (module, class, attr)."""
    for d in LOCKS:
        if d.attr == attr and d.cls == cls and fnmatch.fnmatch(
                relpath, d.path):
            return d
    return None


_ATTR_NAMES: dict[str, set[str]] = {}
for _d in LOCKS:
    _ATTR_NAMES.setdefault(_d.attr, set()).add(_d.name)


def unique_attr_name(attr: str) -> Optional[str]:
    """Hierarchy name for a lock attribute that is unambiguous across
    the whole spec (e.g. ``_fold_lock``) — how cross-object references
    like ``agg._fold_lock`` resolve. ``_lock`` is ambiguous -> None."""
    names = _ATTR_NAMES.get(attr)
    return next(iter(names)) if names and len(names) == 1 else None


def rank_of(name: str) -> Optional[int]:
    return RANKS.get(name)


# -- creation-site table (runtime witness support) -----------------------

def _lock_ctor_kind(node: ast.AST) -> Optional[str]:
    """'lock' / 'rlock' when ``node`` is a threading.Lock()/RLock()
    (or bare Lock()/RLock()) call, else None."""
    if not isinstance(node, ast.Call):
        return None
    fn = node.func
    name = None
    if isinstance(fn, ast.Attribute) and isinstance(fn.value, ast.Name) \
            and fn.value.id == "threading":
        name = fn.attr
    elif isinstance(fn, ast.Name):
        name = fn.id
    if name == "Lock":
        return "lock"
    if name == "RLock":
        return "rlock"
    return None


def iter_lock_sites(tree: ast.AST, relpath: str):
    """Yield (lineno, cls, attr, kind) for every lock construction
    bound to a ``self.X`` attribute or module-level name."""
    class_stack: list[str] = []

    def walk(node):
        is_cls = isinstance(node, ast.ClassDef)
        if is_cls:
            class_stack.append(node.name)
        if isinstance(node, (ast.Assign, ast.AnnAssign)):
            value = node.value
            kind = _lock_ctor_kind(value) if value is not None else None
            if kind is not None:
                targets = (node.targets
                           if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    if isinstance(t, ast.Attribute) and isinstance(
                            t.value, ast.Name) and t.value.id == "self":
                        yield (value.lineno,
                               class_stack[-1] if class_stack else None,
                               t.attr, kind)
                    elif isinstance(t, ast.Name) and not class_stack:
                        yield value.lineno, None, t.id, kind
        for child in ast.iter_child_nodes(node):
            yield from walk(child)
        if is_cls:
            class_stack.pop()

    yield from walk(tree)


def build_site_table(pkg_root) -> dict[tuple[str, int], tuple[str, int]]:
    """(absolute file path, lineno of the Lock() call) ->
    (hierarchy name, rank) for every DECLARED lock in the package —
    how the runtime witness names a lock from its creation frame.
    Pure AST scan; never imports the scanned modules."""
    pkg_root = pathlib.Path(pkg_root).resolve()
    repo_root = pkg_root.parent
    table: dict[tuple[str, int], tuple[str, int]] = {}
    for path in sorted(pkg_root.rglob("*.py")):
        if "__pycache__" in path.parts:
            continue
        relpath = path.relative_to(repo_root).as_posix()
        try:
            tree = ast.parse(path.read_text(), filename=str(path))
        except (SyntaxError, OSError):
            continue
        for lineno, cls, attr, _kind in iter_lock_sites(tree, relpath):
            d = decl_for(relpath, cls, attr)
            if d is not None and d.rank is not None:
                table[(str(path), lineno)] = (d.name, d.rank)
    return table
