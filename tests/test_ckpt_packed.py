"""A full base holds the occupied slots, and only they leave the device
(PR 42): the bucket table is packed where it lives (one chip, or every
shard of ``shard:4`` on its own device), the file holds a fill a bucket
beside the occupied slots' ``keys`` / ``meta`` in bucket order, stored
and not deflated, and every reader follows what the file says: a packed
base of the same topology rebuilds the very rows it was packed from, a
different shard count re-hashes, and a base any earlier tree wrote (a
positional, deflated one; ``tests/data/base_before_pr42.npz`` is one,
written by 84c9021's writer) still loads.
"""

import io
import json
import os
import signal
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from ct_mapreduce_tpu.agg import ckpt
from ct_mapreduce_tpu.agg.aggregator import (
    HostSnapshotAggregator,
    TpuAggregator,
)
from ct_mapreduce_tpu.agg.sharded_agg import ShardedAggregator
from ct_mapreduce_tpu.cmd import storage_statistics
from ct_mapreduce_tpu.config import CTConfig
from ct_mapreduce_tpu.core.types import Issuer
from ct_mapreduce_tpu.ops import buckettable
from ct_mapreduce_tpu.telemetry import metrics, trace
from tests import ckptstate as harness
from tests.test_multilog_round import Compiles, spans

BITS = 12
BATCH = 64
FIXTURE = Path(__file__).parent / "data" / "base_before_pr42.npz"
# What 84c9021 said of the state it saved there (tests/ckptstate.py's
# digest, the drain's total, the table's count).
FIXTURE_DIGEST = \
    "3a0dbe3b1aac5e36c5dfb29de7bc973eeace2778fc0076a8fc1ddf85de003d03"
FIXTURE_TOTAL, FIXTURE_COUNT = 343, 341

LOADS = {"empty": 0.0, "one-batch": None, "half": 0.5, "ninety": 0.9}


@pytest.fixture(autouse=True)
def clean_telemetry():
    trace.disable()
    metrics.set_sink(metrics.InMemSink())
    yield
    trace.disable()
    metrics.set_sink(metrics.InMemSink())


def counters() -> dict:
    return metrics.get_sink().snapshot()["counters"]


def make(topology: str):
    if topology == "one-chip":
        return TpuAggregator(capacity=1 << BITS, batch_size=BATCH,
                             grow_at=0.0)
    n = int(topology.split(":")[1])
    return ShardedAggregator(
        Mesh(np.array(jax.devices()[:n]), ("shard",)),
        capacity=1 << BITS, batch_size=BATCH, grow_at=0.0)


def filled(topology: str, load, seed: int = 42):
    """An aggregator whose table holds ``load`` of its capacity in
    seeded random fingerprints (``None``: one batch of them), an issuer
    and some totals: what a save has to bring back."""
    agg = make(topology)
    n = BATCH if load is None else int(agg.capacity * load)
    rng = np.random.default_rng(seed)
    keys = rng.integers(1, 1 << 32, size=(n, 4), dtype=np.uint32)
    meta = rng.integers(0, 1 << 32, size=(n,), dtype=np.uint32)
    lost = agg._bulk_reinsert(keys, meta)
    assert lost == 0
    agg._table_fill = n
    agg._device_written = bool(n)
    agg.registry.assign_issuer(Issuer.from_spki(b"an spki"))
    agg.issuer_totals[:3] = (n, 7, 11)
    return agg


def table_of(agg):
    table = agg._checkpoint_table()
    return np.asarray(table.rows), np.asarray(table.count)


def key_set(agg) -> set:
    keys, meta = agg._drain_table()
    return {(*k, m) for k, m in zip(keys.tolist(), meta.tolist())}


def pack_np(rows: np.ndarray):
    """The packed form by plain NumPy, what the device's packing is
    held to: ``(fill uint8[buckets], keys uint32[n, 4], meta
    uint32[n])``, occupied slots in bucket order."""
    slots = rows[:, : buckettable.SLOTS * 5].reshape(
        rows.shape[0], buckettable.SLOTS, 5)
    occ = slots[:, :, :4].any(axis=-1)
    live = slots[occ]  # a row-major pick: bucket order
    return occ.sum(axis=-1).astype(np.uint8), live[:, :4], live[:, 4]


def rewrite_positional(packed: str, old: str) -> None:
    """The same base as every writer before PR 42 wrote it: ``keys`` /
    ``meta`` one row a slot, no ``fill``, every member deflated."""
    with np.load(packed, allow_pickle=True) as z:
        members = dict(z)
    rows = buckettable.unpack_np(
        members.pop("fill"), members["keys"], members["meta"])
    slots = rows[:, : buckettable.SLOTS * 5].reshape(-1, 5)
    members["keys"], members["meta"] = slots[:, :4], slots[:, 4]
    with open(old, "wb") as fh:
        np.savez_compressed(fh, **members)


# -- (1) save then load gives the table saved ---------------------------------


@pytest.mark.parametrize("load", list(LOADS))
@pytest.mark.parametrize("topology", ["one-chip", "shard:4"])
def test_a_packed_base_restores_the_very_rows(tmp_path, topology, load):
    """``rows`` whole (the fill word in it), ``count``, the issuer
    totals and the drained keys of the restored table are the saved
    table's; the file holds a fill a bucket and the occupied slots."""
    agg = filled(topology, LOADS[load])
    path = str(tmp_path / "agg.npz")
    trace.enable()
    agg.save_checkpoint(path)
    rows, count = table_of(agg)
    occupied = int(count.sum())
    with np.load(path, allow_pickle=True) as z:
        assert str(z["layout"]) == "bucket"
        assert z["fill"].dtype == np.uint8
        assert z["fill"].shape == (rows.shape[0],)
        assert np.array_equal(z["fill"], rows[:, buckettable.FILL_WORD])
        assert z["keys"].shape == (occupied, 4)
        assert z["meta"].shape == (occupied,)
        assert np.array_equal(z["count"], count)
    (d2h,) = spans("ckpt.d2h")
    assert d2h["args"]["bytes"] == occupied * 20 + rows.shape[0]
    assert (d2h["args"]["occupied"], d2h["args"]["capacity"]) \
        == (occupied, agg.capacity)
    assert counters()["ckpt.base_unpacked"] == 0.0
    cold = make(topology)
    cold.load_checkpoint(path)
    got_rows, got_count = table_of(cold)
    assert np.array_equal(got_rows, rows)
    assert np.array_equal(got_count, count)
    assert np.array_equal(cold.issuer_totals, agg.issuer_totals)
    assert key_set(cold) == key_set(agg)
    assert cold._table_fill == occupied


# -- (2) another shard count re-hashes ----------------------------------------


@pytest.mark.parametrize("writer, reader", [
    ("shard:4", "one-chip"), ("shard:4", "shard:2"), ("one-chip", "shard:4")])
def test_a_packed_base_of_another_topology_is_reinserted(
        tmp_path, writer, reader):
    """A reader with another shard count hands the occupied rows to its
    reinsertion path and holds the same keys; each answers known."""
    agg = filled(writer, 0.5)
    path = str(tmp_path / "agg.npz")
    agg.save_checkpoint(path)
    cold = make(reader)
    cold.load_checkpoint(path)
    want = key_set(agg)
    assert key_set(cold) == want
    probe = np.array([k[:4] for k in sorted(want)[:200]], np.uint32)
    assert cold._device_contains(probe).all()
    assert cold._table_fill == len(want)
    assert int(np.asarray(cold._checkpoint_table().count).sum()) == len(want)


# -- (3) a base from before this PR still loads -------------------------------


@pytest.mark.parametrize("reader", ["host-only", "one-chip", "shard:4"])
def test_a_base_written_by_the_parent_loads(tmp_path, reader):
    """The committed file is 84c9021's writer's: positional ``keys`` /
    ``meta``, deflated, no ``fill``. Every reader restores the state
    that tree said it saved, and a writer that loaded it writes the
    next base packed (an upgrade in place)."""
    with np.load(FIXTURE, allow_pickle=True) as z:
        assert "fill" not in z and z["keys"].shape == (1536, 4)
    cold = (HostSnapshotAggregator(capacity=1 << 10)
            if reader == "host-only" else make(reader))
    cold.load_checkpoint(str(FIXTURE))
    assert harness.ckpt_state_digest(cold) == FIXTURE_DIGEST
    assert cold.drain().total == FIXTURE_TOTAL
    assert int(np.asarray(cold._checkpoint_table().count).sum()) \
        == FIXTURE_COUNT
    if reader == "host-only":
        return
    path = str(tmp_path / "next.npz")
    cold.save_checkpoint(path)
    with np.load(path, allow_pickle=True) as z:
        assert z["keys"].shape == (FIXTURE_COUNT, 4) and "fill" in z
    again = HostSnapshotAggregator(capacity=1 << 10)
    again.load_checkpoint(path)
    assert harness.ckpt_state_digest(again) == FIXTURE_DIGEST


def test_packed_and_positional_bases_give_one_report(tmp_path):
    """``storage-statistics -json`` (what the benchmark compares at
    ``t_durable``) of the packed base and of the same base in the form
    earlier writers gave it."""
    from tests.test_layouts import NOW, entries

    agg = TpuAggregator(capacity=1 << 10, batch_size=BATCH, now=NOW)
    assert agg.ingest(entries(90, "Packed CA")).was_unknown.all()
    packed, old = str(tmp_path / "packed.npz"), str(tmp_path / "old.npz")
    agg.save_checkpoint(packed)
    rewrite_positional(packed, old)
    assert os.path.getsize(old) != os.path.getsize(packed)
    reports = []
    for path in (packed, old):
        ini = tmp_path / "ct.ini"
        ini.write_text(f"backend = tpu\naggStatePath = {path}\n")
        out = io.StringIO()
        assert storage_statistics.report_json(
            CTConfig.load(["-config", str(ini)]), out) == 0
        reports.append(json.loads(out.getvalue()))
    assert reports[0] == reports[1]
    assert reports[0]["totals"]["serials"] == 90


# -- (4) the chain on a packed base -------------------------------------------


def test_a_ck02_chain_on_a_packed_base_replays_to_a_full_save(tmp_path):
    """``want_serials`` on, so ticks after the base are segments: base
    (packed) + two segments restore to the state a full save writes."""
    agg, eh = harness.build_aggregator(400, BITS)
    agg.configure_checkpointing(mode="ck02")
    path = str(tmp_path / "agg.npz")
    agg.save_checkpoint(path)
    harness.ckpt_churn(agg, eh, 37, 400)
    agg.save_checkpoint(path)
    harness.ckpt_churn(agg, eh, 23, 1400)
    agg.save_checkpoint(path)
    with np.load(path, allow_pickle=True) as z:
        assert z["keys"].shape == (400, 4) and "fill" in z
    assert len(ckpt.resolve_chain(path).segments) == 2
    oracle = str(tmp_path / "oracle.npz")
    agg.configure_checkpointing(mode="ck01")
    agg.save_checkpoint(oracle)
    with np.load(oracle, allow_pickle=True) as z:
        assert z["keys"].shape == (460, 4)
    want = harness.ckpt_state_digest(agg)
    for src in (path, oracle):
        cold = HostSnapshotAggregator(capacity=1 << BITS)
        cold.load_checkpoint(src)
        assert harness.ckpt_state_digest(cold) == want


# -- (5) crashes --------------------------------------------------------------

_KILL_CHILD = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, sys.argv[1])
    path = sys.argv[2]

    from tests import ckptstate as harness

    agg, eh = harness.build_aggregator(400, 12)
    agg.configure_checkpointing(mode="ck02", max_chain=1)
    agg.save_checkpoint(path)                       # base
    harness.ckpt_churn(agg, eh, 21, 400)
    agg.save_checkpoint(path)                       # segment 1
    harness.ckpt_churn(agg, eh, 9, 1400)
    print("DIGEST " + harness.ckpt_state_digest(agg), flush=True)
    os.environ["CTMR_CKPT_KILL"] = "base-post-rename"
    agg.save_checkpoint(path)                       # compaction: dies
    raise SystemExit(3)                             # must not be reached
""")


@pytest.mark.timeout(180)
def test_dying_after_the_packed_base_is_renamed_heals_to_it(tmp_path):
    """Kill point ``base-post-rename``, mid-compaction: the new (packed)
    base is on disk under a manifest that names the old one and its
    segment. The loader takes the new base alone, as it did before."""
    repo = str(Path(__file__).resolve().parent.parent)
    path = str(tmp_path / "agg.npz")
    child = tmp_path / "kill_child.py"
    child.write_text(_KILL_CHILD)
    env = {k: v for k, v in os.environ.items() if k != "CTMR_CKPT_KILL"}
    proc = subprocess.run(
        [sys.executable, str(child), repo, path],
        capture_output=True, text=True, timeout=150, env=env)
    assert proc.returncode == -signal.SIGKILL, proc.stdout + proc.stderr
    digest = next(line.split(" ", 1)[1]
                  for line in proc.stdout.splitlines()
                  if line.startswith("DIGEST "))
    stale = ckpt.read_manifest(path)
    assert stale["baseSha256"] != ckpt.file_sha256(path)
    assert len(ckpt.resolve_chain(path).segments) == 0
    cold = HostSnapshotAggregator(capacity=1 << BITS)
    cold.load_checkpoint(path)
    assert harness.ckpt_state_digest(cold) == digest
    with np.load(path, allow_pickle=True) as z:
        assert z["keys"].shape == (430, 4)


def test_a_torn_temp_file_leaves_the_last_base(tmp_path, monkeypatch):
    """A write that dies half way: the save raises, the temp file is
    gone, the base on disk is the last good one, and the next save
    anchors with a full base that holds everything."""
    agg, eh = harness.build_aggregator(300, BITS)
    agg.configure_checkpointing(mode="ck02")
    path = str(tmp_path / "agg.npz")
    agg.save_checkpoint(path)
    before = harness.ckpt_state_digest(agg)
    sha = ckpt.file_sha256(path)
    harness.ckpt_churn(agg, eh, 17, 300)
    agg._ckpt_mark_dirty_lost("the test wants a base")
    real = ckpt.write_npz

    def torn(fh, members, stored=()):
        fh.write(b"PK\x03\x04 half a base")
        fh.flush()
        raise OSError("disk went away")

    monkeypatch.setattr(ckpt, "write_npz", torn)
    with pytest.raises(OSError, match="disk went away"):
        agg.save_checkpoint(path)
    assert sorted(os.listdir(tmp_path)) == [
        "agg.npz", "agg.npz.ckmanifest.json"]
    assert ckpt.file_sha256(path) == sha
    cold = HostSnapshotAggregator(capacity=1 << BITS)
    cold.load_checkpoint(path)
    assert harness.ckpt_state_digest(cold) == before
    monkeypatch.setattr(ckpt, "write_npz", real)
    trace.enable()
    agg.save_checkpoint(path)
    assert [s["args"]["kind"] for s in spans("ckpt.save")][-1] == "full"
    cold = HostSnapshotAggregator(capacity=1 << BITS)
    cold.load_checkpoint(path)
    assert harness.ckpt_state_digest(cold) == harness.ckpt_state_digest(agg)


# -- (6) one program, and the counter that says it engaged --------------------


@pytest.mark.parametrize("topology", ["one-chip", "shard:4"])
def test_saves_of_one_and_of_many_chunks_run_one_program(
        tmp_path, monkeypatch, topology):
    """With a chunk of 256 rows: the first save compiles the two
    programs (index, chunk); a save of one chunk and one of many
    compile nothing more, and the spans count the chunks that crossed."""
    monkeypatch.setattr(buckettable, "PACK_CHUNK", 256)
    shards = 1 if topology == "one-chip" else 4
    agg = filled(topology, None)
    path = str(tmp_path / "agg.npz")
    trace.enable()
    with Compiles() as first:
        agg.save_checkpoint(path)
    # index and chunk (the index alone may be an earlier test's: its
    # shape follows the table's, the chunk's the 256 set here)
    assert 1 <= first.n <= 2
    with Compiles() as one_chunk:
        agg._ckpt_mark_dirty_lost("the test wants a base")
        agg.save_checkpoint(path)
    rng = np.random.default_rng(7)
    more = rng.integers(1, 1 << 32, size=(1900, 4), dtype=np.uint32)
    assert agg._bulk_reinsert(more, more[:, 0]) == 0
    with Compiles() as many_chunks:
        agg._ckpt_mark_dirty_lost("the test wants a base")
        agg.save_checkpoint(path)
    assert (one_chunk.n, many_chunks.n) == (0, 0)
    counts = np.asarray(agg._checkpoint_table().count).reshape(-1)
    assert [s["args"]["chunks"] for s in spans("ckpt.d2h")] == [
        shards, shards, sum(-(-int(c) // 256) for c in counts)]
    assert spans("ckpt.d2h")[-1]["args"]["chunks"] >= 8
    cold = make(topology)
    cold.load_checkpoint(path)
    assert np.array_equal(table_of(cold)[0], table_of(agg)[0])


@pytest.mark.parametrize("layout, unpacked", [("bucket", 0.0), ("open", 1.0)])
def test_base_unpacked_counts_the_whole_table_copies(
        tmp_path, monkeypatch, layout, unpacked):
    """``ckpt.base_unpacked``: 0 at every full save of the bucket
    layout, 1 where the layout does not fill contiguously and the whole
    table crossed (``ckpt.d2h`` says its bytes); either restores."""
    monkeypatch.setenv("CTMR_TABLE", layout)
    agg = filled("one-chip", 0.3)
    path = str(tmp_path / "agg.npz")
    trace.enable()
    agg.save_checkpoint(path)
    assert counters()["ckpt.base_unpacked"] == unpacked
    (d2h,) = spans("ckpt.d2h")
    rows, count = table_of(agg)
    if layout == "open":
        assert d2h["args"]["bytes"] == rows.nbytes
        with np.load(path, allow_pickle=True) as z:
            assert "fill" not in z and z["keys"].shape == (rows.shape[0], 4)
    else:
        assert d2h["args"]["bytes"] == int(count) * 20 + rows.shape[0]
    cold = make("one-chip")
    cold.load_checkpoint(path)
    assert key_set(cold) == key_set(agg)
    assert np.array_equal(table_of(cold)[0], rows)


def test_a_table_whose_fill_words_lie_is_copied_whole(tmp_path):
    """The pack trusts the cached fill word, and checks it: where the
    fills do not add up to the table's count the save copies the whole
    table, counts itself, and loses nothing."""
    agg = filled("one-chip", 0.3)
    rows, count = table_of(agg)
    bad = rows.copy()
    bad[:, buckettable.FILL_WORD] = 0
    agg.table = buckettable.BucketTable(
        rows=jax.numpy.asarray(bad), count=agg.table.count)
    path = str(tmp_path / "agg.npz")
    agg.save_checkpoint(path)
    assert counters()["ckpt.base_unpacked"] == 1.0
    cold = make("one-chip")
    cold.load_checkpoint(path)
    assert np.array_equal(table_of(cold)[0], rows)  # the reader recounts
    assert np.array_equal(table_of(cold)[1], count)


# -- (7) the packing itself, against its host mirror --------------------------


@pytest.mark.parametrize("buckets, load, chunk", [
    (64, 0.0, 1536), (64, 0.6, 100), (4096, 0.3, 98304), (4096, 0.9, 4096),
    (1 << 15, 0.5, 1 << 18)])
def test_the_device_packs_what_the_host_mirror_packs(buckets, load, chunk):
    """``pack_index`` + ``pack_chunk`` over tables whose search index
    has one, two and three levels, chunk by chunk, against plain NumPy;
    ``unpack_np`` gives the rows back."""
    rng = np.random.default_rng(buckets + int(load * 10))
    rows = np.zeros((buckets, buckettable.ROW_WORDS), np.uint32)
    n = int(buckets * buckettable.SLOTS * load)
    keys = rng.integers(0, 1 << 32, size=(n, 4), dtype=np.uint32)
    lost = buckettable.bulk_insert_np(rows, keys, keys[:, 3], max_probes=64)
    fill, want_keys, want_meta = pack_np(rows)
    assert want_keys.shape[0] == n - lost
    assert np.array_equal(
        buckettable.unpack_np(fill, want_keys, want_meta), rows)
    device_rows = jax.numpy.asarray(rows)
    got_fill, index = buckettable.pack_index_jit(device_rows)
    assert np.array_equal(np.asarray(got_fill), fill)
    total = int(index[-1][-1])
    assert total == n - lost
    assert len(index) == (1 if buckets <= 1024 else 2 if buckets <= 1 << 17
                          else 3)
    words = [np.asarray(buckettable.pack_chunk_jit(
        device_rows, index, np.int32(start), chunk=chunk)).reshape(5, chunk)
        for start in range(0, total, chunk)]
    packed = (np.concatenate(words, axis=1) if words
              else np.zeros((5, 0), np.uint32))
    assert np.array_equal(packed[:4, :total].T, want_keys)
    assert np.array_equal(packed[4, :total], want_meta)
    assert not packed[:, total:].any()


def test_unpack_refuses_fills_that_do_not_add_up():
    with pytest.raises(ValueError, match="packed base"):
        buckettable.unpack_np(np.array([2, 1], np.uint8),
                              np.zeros((2, 4), np.uint32),
                              np.zeros((2,), np.uint32))
