"""The benchmark's reader of a span's CPU time and arguments and the
seven metrics of the layer "host threads (the GIL)"
(``benchmark/tests/test_threads.py``) as tier-1 tests; see
``test_benchmark_harness.py``.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.tests import test_threads as theirs  # noqa: E402,F401
from benchmark.tests.test_threads import *  # noqa: E402,F401,F403

PAGES_WALKED = {
    "name": "decode.pages_walked", "unit": "pages", "better": "lower",
    "source": "program_counter", "layer": "native decode + pack",
    "moves": "ingest_entries_per_s", "workloads": list(theirs.ALL_CELLS)}


UNPACKED_SAVES = {
    "name": "ckpt.unpacked_saves", "unit": "n", "better": "lower",
    "source": "program_counter", "layer": "checkpoint", "moves": "setup_s",
    "workloads": [*theirs.ALL_CELLS, "backfill-3log-shard4"]}


FP_FALLBACK = {
    "name": "fp.fallback_lanes", "unit": "n", "better": "lower",
    "source": "program_counter", "layer": "query plane",
    "moves": "ingest_entries_per_s",
    "workloads": ["backfill-1log-query", "backfill-3log-query-shard4"]}


FRONT_CPU_MESH = {
    "name": "qshard4.front_cpu_ms_per_request", "unit": "ms",
    "better": "lower", "source": "program_span",
    "layer": "host threads (the GIL)", "moves": "ingest_entries_per_s",
    "workloads": ["backfill-3log-query-shard4"]}


POOL_REQUESTS = {
    "name": "front.pool_requests", "unit": "n", "better": "lower",
    "source": "program_counter", "layer": "query plane",
    "moves": "ingest_entries_per_s",
    "workloads": ["backfill-1log-query", "backfill-3log-query-shard4"]}


@pytest.mark.parametrize("increments, want", [
    ([], "ABSENT"),  # the parent: a program that does not count them
    ([0.0, 0.0, 0.0], 0.0),  # every chunk went over as a page table
    ([0.0, 128.0, 3.0], 131.0)])
def test_pages_walked_reads_the_rounds_increments(increments, want):
    """The reader on what the harness's emitter records: no increment
    of the counter in the whole round, not one of 0, leaves the metric
    out by name; else the sum over ``[t_open, t_durable]``."""
    out = {"t_open": 10.0, "t_durable": 20.0,
           "counters": [(9.0, "decode.pages_walked", 5.0) for _ in increments]
           + [(11.0 + k, "decode.pages_walked", v)
              for k, v in enumerate(increments)]
           + [(12.0, "decode.pages_tabled", 128.0)]}
    metrics, absent = theirs.layers.read_metrics(
        [PAGES_WALKED], "backfill-3log", {"out": out})
    if want == "ABSENT":
        assert absent == ["decode.pages_walked"] and metrics == {}
    else:
        assert absent == [] and metrics == {
            "decode.pages_walked": {"value": want, "unit": "pages"}}


@pytest.mark.parametrize("increments, want", [
    ([], "ABSENT"),  # the parent: a program that writes no packed base
    ([0.0], 0.0),  # the round's one full save was packed
    ([0.0, 1.0], 1.0)])  # one save copied the whole table out
@pytest.mark.parametrize("cell", UNPACKED_SAVES["workloads"])
def test_unpacked_saves_reads_the_rounds_increments(cell, increments, want):
    """``ckpt.unpacked_saves`` in each of the four cells: the warm-up
    round's save lies before ``t_open`` and is not counted; no
    increment in the round leaves the metric out by name."""
    out = {"t_open": 10.0, "t_durable": 20.0,
           "counters": [(4.0, "ckpt.base_unpacked", 1.0)] * bool(increments)
           + [(19.0 + k / 2, "ckpt.base_unpacked", v)
              for k, v in enumerate(increments)]
           + [(19.5, "ckpt.full_saves", 1.0)]}
    metrics, absent = theirs.layers.read_metrics(
        [UNPACKED_SAVES], cell, {"out": out})
    if want == "ABSENT":
        assert absent == ["ckpt.unpacked_saves"] and metrics == {}
    else:
        assert absent == [] and metrics == {
            "ckpt.unpacked_saves": {"value": want, "unit": "n"}}


@pytest.mark.parametrize("increments, want", [
    ([], "ABSENT"),  # the parent: its fingerprint says nothing
    ([0.0, 0.0, 0.0], 0.0),  # every batch of lookups took the native call
    ([0.0, 2.0, 1.0], 3.0)])  # two batches of 2 and 1 lanes took NumPy's
@pytest.mark.parametrize("cell", FP_FALLBACK["workloads"])
def test_fp_fallback_lanes_reads_the_rounds_increments(cell, increments, want):
    """``fp.fallback_lanes`` in each of the two query cells, on a
    recorded ring: the generators' warm-up lookups lie before ``t_open``
    and are not counted; no increment in the round, not one of 0,
    leaves the metric out by name. The cells without queries do not
    list it."""
    out = {"t_open": 10.0, "t_durable": 20.0,
           "counters": [(4.0, "fp.fallback_lanes", 16.0)] * bool(increments)
           + [(11.0 + k, "fp.fallback_lanes", v)
              for k, v in enumerate(increments)]
           + [(11.5, "fp.lanes", 2.0)]}
    metrics, absent = theirs.layers.read_metrics(
        [FP_FALLBACK], cell, {"out": out})
    if want == "ABSENT":
        assert absent == ["fp.fallback_lanes"] and metrics == {}
    else:
        assert absent == [] and metrics == {
            "fp.fallback_lanes": {"value": want, "unit": "n"}}
    assert theirs.layers.read_metrics(
        [FP_FALLBACK], "backfill-3log", {"out": out}) == ({}, [])


@pytest.mark.parametrize("increments, want", [
    ([], "ABSENT"),  # the parent: a thread a connection, no such counter
    ([0.0, 0.0, 0.0], 0.0),  # every request was served by the loop
    ([0.0, 1.0, 0.0], 1.0)])  # one left it for the pool
@pytest.mark.parametrize("cell", POOL_REQUESTS["workloads"])
def test_pool_requests_reads_the_rounds_increments(cell, increments, want):
    """``front.pool_requests`` in each of the two query cells: the
    generators' warm-up requests lie before ``t_open`` and are not
    counted; a program whose front never adds to the counter, not even
    0, leaves the metric out by name. The cells without queries do not
    list it."""
    out = {"t_open": 10.0, "t_durable": 20.0,
           "counters": [(4.0, "front.pool_requests", 1.0)] * bool(increments)
           + [(11.0 + k, "front.pool_requests", v)
              for k, v in enumerate(increments)]
           + [(11.0 + k, "front.requests", 1.0)
              for k, _ in enumerate(increments)]}
    metrics, absent = theirs.layers.read_metrics(
        [POOL_REQUESTS], cell, {"out": out})
    if want == "ABSENT":
        assert absent == ["front.pool_requests"] and metrics == {}
    else:
        assert absent == [] and metrics == {
            "front.pool_requests": {"value": want, "unit": "n"}}
    assert theirs.layers.read_metrics(
        [POOL_REQUESTS], "backfill-3log-shard4", {"out": out}) == ({}, [])


def test_the_mesh_cells_front_cpu_reads_what_the_one_chip_cells_does():
    """``qshard4.front_cpu_ms_per_request`` on the recorded ring and on
    the hand-made window: the number ``front.cpu_ms_per_request`` reads
    there, in its own cell alone; a ring without the ``front.`` family
    (a program older than the span) leaves it out by name, as it does
    the one-chip cell's. A connection the loop served in turns and one a
    thread lived for are the same span to the reader: ``tdur`` summed
    over the connections that end in the window, by their ``requests``."""
    ctx = theirs.recorded()
    one_chip = next(m for m in theirs.bench_json()["per_layer"]
                    if m["name"] == "front.cpu_ms_per_request")
    mesh, absent = theirs.layers.read_metrics(
        [FRONT_CPU_MESH], "backfill-3log-query-shard4", ctx)
    flat, _ = theirs.layers.read_metrics(
        [one_chip], "backfill-1log-query", ctx)
    assert absent == [] and mesh == {FRONT_CPU_MESH["name"]: {
        "value": pytest.approx(0.8833897142857143), "unit": "ms"}}
    assert mesh[FRONT_CPU_MESH["name"]] \
        == flat["front.cpu_ms_per_request"]
    assert theirs.layers.read_metrics(
        [FRONT_CPU_MESH], "backfill-1log-query", ctx) == ({}, [])
    window = theirs.ctx_of(theirs.EVENTS)
    assert theirs.layers.read_metrics(
        [FRONT_CPU_MESH], "backfill-3log-query-shard4", window)[0][
            FRONT_CPU_MESH["name"]]["value"] == pytest.approx(1.5)
    older = theirs.ctx_of([e for e in theirs.EVENTS
                           if e["name"] != "front.conn"])
    assert theirs.layers.read_metrics(
        [FRONT_CPU_MESH], "backfill-3log-query-shard4", older) \
        == ({}, [FRONT_CPU_MESH["name"]])


pytestmark = [pytest.mark.timeout(300),
              pytest.mark.usefixtures("benchmark_checkout")]
