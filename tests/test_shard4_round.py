"""The sharded deployment ``loglist3-shard4`` through ct-fetch's own path
(``build_aggregator`` picks the mesh from ``meshShape = shard:4``,
``LogSyncEngine``, ``AggregatorSink``) on four of the CPU's virtual
devices: a round of three logs that ends in a short chunk has the counts
and cursors of the plain reference (``tests/reference_multilog.py``, a
dict of sets and a cursor a log) and writes one checkpoint, which
``storage-statistics -json`` reads whole; the four shards' shares add up
to the one-chip table's; a batch's rows cross to the device once and are
never read back; a full save hands the writer the row-sharded table, not
a copy of it on one device; lanes past the routing quota are counted and
their serials still counted exactly.
"""

import io
import json
import warnings

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
from ct_mapreduce_tpu.agg.sharded import shard_of_np
from ct_mapreduce_tpu.agg.sharded_agg import ShardedAggregator
from ct_mapreduce_tpu.cmd import storage_statistics
from ct_mapreduce_tpu.config import CTConfig
from ct_mapreduce_tpu.ingest.sync import AggregatorSink
from ct_mapreduce_tpu.models.ingest_model import build_aggregator
from ct_mapreduce_tpu.native import leafpack
from ct_mapreduce_tpu.ops import buckettable
from ct_mapreduce_tpu.telemetry import metrics, trace
from tests import test_multilog_round as multilog
from tests.test_multilog_round import (
    BATCH,
    LENGTHS,
    NOW,
    PAGE,
    Compiles,
    make_logs,
    raw_page,
    reference_of,
    spans,
)

SHARDS = 4
ROW_WIDTH = AggregatorSink.PAD_LEN  # the templates' leaves need the wide row

needs_native = pytest.mark.skipif(
    leafpack.load_native() is None,
    reason="the raw-batch path needs the native decoder")


@pytest.fixture(autouse=True)
def clean_telemetry():
    trace.disable()
    metrics.set_sink(metrics.InMemSink())
    trace.set_process_attrs(**dict.fromkeys(trace.get_process_attrs()))
    yield
    trace.disable()
    metrics.set_sink(metrics.InMemSink())


def mesh4() -> Mesh:
    return Mesh(np.array(jax.devices()[:SHARDS]), ("shard",))


def sharded(batch: int = BATCH, **kw) -> ShardedAggregator:
    return ShardedAggregator(mesh4(), capacity=1 << 12, batch_size=batch,
                             now=NOW, **kw)


def counters() -> dict:
    return metrics.get_sink().snapshot()["counters"]


class Run(multilog.Run):
    """``tests/test_multilog_round.py``'s engine, its aggregator built
    as ``ct-fetch`` builds it from the configuration's ``meshShape``."""

    def __init__(self, state_dir):
        self.config = CTConfig(
            backend="tpu", table_bits=12, batch_size=BATCH,
            mesh_shape=f"shard:{SHARDS}",
            agg_state_path=str(state_dir / "agg.npz"))
        agg = build_aggregator(self.config)
        assert isinstance(agg, ShardedAggregator)
        agg._fixed_now = NOW
        super().__init__(state_dir, sink=AggregatorSink(agg, flush_size=BATCH))
        self.agg = agg  # the one the sink feeds and ``save`` writes


def feed(sink: AggregatorSink, pages) -> None:
    for raw in pages:
        sink.store_raw_batch(raw)
    sink.flush()


def pages_of(logs, order) -> list:
    """Every page of ``logs`` as the store thread could meet them:
    ``order`` picks which log gives its next page."""
    left = {k: list(range(0, log.size, PAGE)) for k, log in enumerate(logs)}
    out = []
    for k in order:
        if left[k]:
            start = left[k].pop(0)
            log = logs[k]
            out.append(raw_page(log.url, start, json.loads(log.body(
                start, min(start + PAGE, log.size) - 1))["entries"]))
    assert not any(left.values())
    return out


# -- (1) ct-fetch's own path against the plain reference ----------------------


@needs_native
@pytest.mark.parametrize("seed", [11, 2147483659, 31337])
def test_a_round_on_four_shards_agrees_with_the_plain_reference(tmp_path,
                                                                seed):
    """Three logs of unequal length (the total no whole number of
    batches, so the round ends in a short chunk), downloaders and store
    thread as ct-fetch runs them: counts per (issuer, expiry date) and
    cursors are the reference's, the round wrote one full checkpoint,
    and the report read from the files alone says the same."""
    trace.enable()
    logs = make_logs(seed)
    ref = reference_of(logs)
    run = Run(tmp_path)
    run.round(logs)
    snap = run.agg.drain()
    run.close()
    assert ref.entries == sum(LENGTHS) and ref.unique() < ref.entries
    assert snap.counts == ref.counts()
    assert snap.total == ref.unique()
    assert run.cursors(logs) == ref.cursors
    assert [s["args"]["kind"] for s in spans("ckpt.save")] == ["full", "noop"]
    (d2h,) = spans("ckpt.d2h")
    assert d2h["args"]["shards"] == SHARDS
    assert counters()["ingest.partial_batches"] >= 1
    # storage-statistics -json: a reader that sees no chip and no mesh.
    out = io.StringIO()
    assert storage_statistics.report_json(run.config, out) == 0
    report = json.loads(out.getvalue())
    assert report["totals"]["serials"] == ref.unique()
    by_issuer: dict[str, int] = {}
    for (issuer, _exp), n in ref.counts().items():
        by_issuer[issuer] = by_issuer.get(issuer, 0) + n
    assert {i["id"]: i["serials"] for i in report["issuers"]} == by_issuer
    assert {e for i in report["issuers"] for e in i["expDates"]} \
        == {exp for _issuer, exp in ref.counts()}
    with np.load(run.path, allow_pickle=True) as z:
        assert int(z["n_shards"]) == SHARDS


@needs_native
@pytest.mark.parametrize("order", [
    [0, 1] * 30, [1, 0, 0] * 20, [0] * 15 + [1] * 15],
    ids=["in-turn", "two-to-one", "one-after-the-other"])
def test_counts_are_the_references_whatever_the_interleaving(order):
    """The store thread's view, fed by hand: however two logs' pages
    meet in a batch, the sharded table counts what the reference does."""
    logs = make_logs(41, (232, 136))
    ref = reference_of(logs)
    agg = sharded()
    sink = AggregatorSink(agg, flush_size=BATCH)
    feed(sink, pages_of(logs, order))
    snap = agg.drain()
    sink.close()
    assert snap.counts == ref.counts() and snap.total == ref.unique()


# -- (2) the shares add up ----------------------------------------------------


@needs_native
def test_the_four_shards_shares_add_up_to_the_one_chip_table():
    """Every key sits in the shard its fingerprint hashes to, the four
    key sets are pairwise disjoint, and their union and the per-issuer
    counts are the one-chip aggregator's on the same entries."""
    logs = make_logs(42)
    pages = pages_of(logs, [0, 1] * 30)
    mesh_agg, chip_agg = sharded(), TpuAggregator(
        capacity=1 << 12, batch_size=BATCH, now=NOW)
    for agg in (mesh_agg, chip_agg):
        sink = AggregatorSink(agg, flush_size=BATCH)
        feed(sink, pages)
        sink.close()
    keys, _meta = mesh_agg.dedup.drain_np()
    rows = np.asarray(mesh_agg.dedup.rows)
    per_shard = rows.shape[0] // SHARDS
    shares = []
    for shard in range(SHARDS):
        block = rows[shard * per_shard:(shard + 1) * per_shard]
        if mesh_agg.dedup.layout == "bucket":
            block = block[:, :buckettable.SLOTS * 5]
        slots = block.reshape(-1, 5)
        mine = slots[slots[:, :4].any(axis=1), :4]
        assert (shard_of_np(mine, SHARDS) == shard).all()
        shares.append({tuple(k) for k in mine.tolist()})
    assert sum(len(s) for s in shares) == len(set().union(*shares)) \
        == keys.shape[0] == mesh_agg.dedup.total_count()
    assert list(np.asarray(mesh_agg.dedup.count)) == [len(s) for s in shares]
    chip_keys, _ = chip_agg._drain_table()
    assert set().union(*shares) == {tuple(k) for k in chip_keys.tolist()}
    assert mesh_agg.drain().counts == chip_agg.drain().counts
    np.testing.assert_array_equal(
        mesh_agg.issuer_totals[:16], chip_agg.issuer_totals[:16])


# -- (3) rows cross once ------------------------------------------------------


@needs_native
def test_rows_cross_to_their_shards_once_and_one_program_serves():
    """A whole batch is put on its shards by the decode side and handed
    through untouched; a chunk short of the batch is padded on the host
    and put once by the step. Either way the step gets a row-sharded
    ``jax.Array``, nothing reads rows back, the bytes put are the rows'
    own, and the second kind compiles nothing."""
    agg = sharded()
    sink = AggregatorSink(agg, flush_size=BATCH)
    pages = multilog.template_pages(BATCH + 24)
    whole, short = pages[:BATCH // PAGE], pages[BATCH // PAGE:]
    want = NamedSharding(agg.mesh, PartitionSpec("shard"))
    handed, stepped = [], []
    real_packed, real_step = agg._device_step_packed, agg.dedup.step

    def packed(batch):
        handed.append(batch.data)
        return real_packed(batch)

    def step(data, *args, **kw):
        stepped.append(data)
        return real_step(data, *args, **kw)

    agg._device_step_packed, agg.dedup.step = packed, step
    trace.enable()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with Compiles() as first:
            feed(sink, whole)
        with Compiles() as second:
            feed(sink, short)
    sink.close()
    assert first.n >= 1 and second.n == 0
    assert len([k for k in agg.dedup._step_cache if k[0] == BATCH]) == 1 \
        == len(agg.dedup._step_cache)
    # The whole batch came placed; the short chunk as NumPy rows.
    assert isinstance(handed[0], jax.Array) and handed[0] is stepped[0]
    assert isinstance(handed[1], np.ndarray)
    for rows in stepped:
        assert isinstance(rows, jax.Array)
        assert rows.shape == (BATCH, ROW_WIDTH)
        assert rows.sharding.is_equivalent_to(want, rows.ndim)
        assert {s.data.shape for s in rows.addressable_shards} \
            == {(BATCH // SHARDS, ROW_WIDTH)}
    got = counters()
    assert got["shard.row_bytes_d2h"] == 0.0
    assert got["shard.row_bytes_h2d"] == 2 * BATCH * ROW_WIDTH
    assert got["shard.lanes_routed"] == BATCH + 24
    assert got["shard.dispatch_spill_lanes"] == 0.0
    puts = spans("shard.put")
    assert [(p["args"]["bytes"], p["args"]["shards"]) for p in puts] \
        == [(BATCH * ROW_WIDTH, SHARDS)] * 2
    assert [(s["args"]["shards"], s["args"]["lanes"])
            for s in spans("mesh.step")] == [(SHARDS, BATCH)] * 2


@needs_native
@pytest.mark.parametrize("restore_into", ["one-chip", "two-shards"])
def test_a_full_save_reads_the_shards_where_they_live(tmp_path, restore_into):
    """The writer is handed the row-sharded table itself (four shards,
    each on a device of its own) and the aggregator holds no copy of it
    on one device meanwhile; the file says ``n_shards`` 4 and restores,
    by reinsertion, into one chip and into two shards with the same
    keys."""
    logs = make_logs(43)
    agg = sharded()
    sink = AggregatorSink(agg, flush_size=BATCH)
    feed(sink, pages_of(logs, [0, 1] * 30))
    sink.close()
    seen = []
    real = agg._write_npz

    def write_npz(fh, host_items):
        table = agg._checkpoint_table()
        seen.append((table.rows, table.count, agg.table))
        return real(fh, host_items)

    agg._write_npz = write_npz
    path = str(tmp_path / "agg.npz")
    trace.enable()
    agg.save_checkpoint(path)
    ((rows, count, table),) = seen
    assert table is None  # no single-device stand-in of the table
    assert rows is agg.dedup.rows and count is agg.dedup.count
    assert len(rows.sharding.device_set) == SHARDS
    assert len({s.device for s in rows.addressable_shards}) == SHARDS
    assert {s.data.shape[0] for s in rows.addressable_shards} \
        == {rows.shape[0] // SHARDS}
    (d2h,) = spans("ckpt.d2h")
    fills = [int(c) for c in np.asarray(count)]
    # Since PR 42 a shard is packed on its own chip and only its
    # occupied slots (20 B each) and a byte a bucket cross, one chunk a
    # shard here; before, the span said the whole table's bytes.
    assert d2h["args"] == {
        "bytes": sum(fills) * 20 + rows.shape[0], "shards": SHARDS,
        "occupied": sum(fills), "capacity": agg.dedup.capacity,
        "chunks": SHARDS}
    assert counters()["ckpt.base_unpacked"] == 0.0
    gauges = metrics.get_sink().snapshot()["gauges"]
    assert (gauges["shard.fill_min"], gauges["shard.fill_max"]) \
        == (min(fills), max(fills))
    assert gauges["shard.fill_mean"] == pytest.approx(sum(fills) / SHARDS)
    with np.load(path, allow_pickle=True) as z:
        assert int(z["n_shards"]) == SHARDS
        assert list(z["count"]) == fills
        # A packed base: a fill a bucket, the occupied slots beside it.
        assert z["fill"].shape[0] * buckettable.SLOTS == agg.dedup.capacity
        assert z["keys"].shape[0] == z["meta"].shape[0] == sum(fills)
    if restore_into == "one-chip":
        cold = TpuAggregator(capacity=1 << 12, batch_size=BATCH, now=NOW)
    else:
        cold = ShardedAggregator(
            Mesh(np.array(jax.devices()[:2]), ("shard",)),
            capacity=1 << 12, batch_size=BATCH, now=NOW)
    cold.load_checkpoint(path)
    want, _ = agg.dedup.drain_np()
    got, _ = cold._drain_table()
    assert {tuple(k) for k in got.tolist()} \
        == {tuple(k) for k in want.tolist()}
    assert cold.drain().counts == agg.drain().counts


# -- (4) lanes past the routing quota -----------------------------------------


@needs_native
def test_spilled_lanes_are_counted_and_their_serials_still_exact():
    """With the quota at its floor (8 a (source, destination) pair
    against 16 expected) about half of a 256-lane batch spills to the
    exact host lane: the counter says how many, the routed lanes make up
    the rest, and the counts are still the reference's."""
    batch = 256
    logs = make_logs(44, (256, 256))
    ref = reference_of(logs)
    agg = sharded(batch, dispatch_factor=0.0)
    sink = AggregatorSink(agg, flush_size=batch)
    feed(sink, pages_of(logs, [0, 1] * 16))
    snap = agg.drain()
    sink.close()
    got = counters()
    spilled = got["shard.dispatch_spill_lanes"]
    assert spilled == agg.metrics["dispatch_spill"] > 50
    assert got["shard.lanes_routed"] + spilled == 512
    assert got["shard.lanes_routed"] <= 2 * SHARDS * SHARDS * 8
    assert snap.counts == ref.counts() and snap.total == ref.unique()
