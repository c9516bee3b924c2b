"""A restore follows the entries, not the slots (PR 50): a packed
base's ``keys`` / ``meta`` / ``fill`` go to the device as the file
holds them, a piece of the stream at a time, and
``buckettable.unpack_rows`` builds the bucket rows there, into the
table the constructor made. ``unpack_np`` is the plain reference it is
held to word for word, and what the host-only reader keeps: residency
decides the path, nothing else does.
"""

import gc

import jax
import numpy as np
import pytest

from ct_mapreduce_tpu.agg.aggregator import (
    HostSnapshotAggregator,
    TpuAggregator,
)
from ct_mapreduce_tpu.core.types import Issuer
from ct_mapreduce_tpu.ops import buckettable as bt
from ct_mapreduce_tpu.telemetry import metrics, trace
from tests import ckptstate

BITS = 11  # 128 buckets, 3,072 slots


@pytest.fixture(autouse=True)
def clean_telemetry():
    trace.disable()
    metrics.set_sink(metrics.InMemSink())
    yield
    trace.disable()
    metrics.set_sink(metrics.InMemSink())


def counters() -> dict:
    return metrics.get_sink().snapshot()["counters"]


def spans(name: str = "") -> list[dict]:
    return [e for e in trace.snapshot_events()
            if e["ph"] == "X" and (not name or e["name"] == name)]


# -- (1) the program against the plain reference ------------------------------


def fills_of(case: str, nb: int, rng) -> np.ndarray:
    if case == "empty":
        fill = np.zeros(nb)
    elif case == "full":
        fill = np.full(nb, bt.SLOTS)
    elif case == "one-full":
        fill = np.zeros(nb)
        fill[nb // 3] = bt.SLOTS
    elif case == "ends-mid-block":
        # The rows end inside a piece, in a bucket inside a block; every
        # bucket after it is empty.
        fill = np.minimum(rng.poisson(12, nb), bt.SLOTS)
        fill[nb // 2 + 3:] = 0
    else:
        fill = np.minimum(rng.poisson(float(case) * bt.SLOTS, nb), bt.SLOTS)
    return fill.astype(np.uint8)


def packed(case: str, nb: int, seed: int = 7):
    rng = np.random.default_rng(seed)
    fill = fills_of(case, nb, rng)
    n = int(fill.sum())
    keys = rng.integers(1, 1 << 32, size=(n, 4), dtype=np.uint32)
    meta = rng.integers(0, 1 << 32, size=(n,), dtype=np.uint32)
    return fill, keys, meta


def unpack_on_device(fill, keys, meta, piece: int, block: int):
    """The pieces through the program, as the aggregator drives them,
    over a table that holds something else in every word."""
    nb = fill.shape[0]
    base, pieces = bt.packed_pieces(fill, keys, meta, piece)
    rows = jax.numpy.full((nb, bt.ROW_WORDS), 0xDEADBEEF, jax.numpy.uint32)
    fill_d, base_d = jax.device_put(fill), jax.device_put(base)
    n = 0
    for start, lo, hi, keys_piece, meta_piece in pieces:
        assert keys_piece.shape == ((piece + bt.UNPACK_HALO) // 32, 128)
        assert meta_piece.shape == ((piece + bt.UNPACK_HALO) // 128, 128)
        rows = bt.unpack_rows(rows, fill_d, base_d, jax.device_put(keys_piece),
                              jax.device_put(meta_piece), start, lo, hi,
                              block=block)
        n += 1
    return np.asarray(rows), n


LOADS = ["empty", "0.05", "0.5", "0.7", "full", "one-full", "ends-mid-block"]


@pytest.mark.parametrize("case", LOADS)
@pytest.mark.parametrize("nb, piece, block", [(64, 256, 16),
                                              (4096, 8192, 512)])
def test_the_device_unpacks_what_unpack_np_unpacks(case, nb, piece, block):
    """Word for word, ``FILL_WORD`` included, whatever the table held
    before; the stream is cut into several pieces and the buckets into
    several blocks, so buckets straddle both kinds of edge."""
    fill, keys, meta = packed(case, nb)
    want = bt.unpack_np(fill, keys, meta)
    got, pieces = unpack_on_device(fill, keys, meta, piece, block)
    assert pieces == keys.shape[0] // piece + 1
    assert np.array_equal(got[:, bt.FILL_WORD], fill)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("nb", [1, 16, 1 << 13])
def test_a_small_table_is_one_piece_at_the_shipped_constants(nb):
    fill, keys, meta = packed("0.5", nb)
    piece = bt.unpack_piece_rows(nb)
    assert piece % bt.ROW_WORDS == 0 and piece >= nb * bt.SLOTS
    got, pieces = unpack_on_device(fill, keys, meta, piece,
                                   min(nb, bt.UNPACK_BLOCK))
    assert pieces == 1
    assert np.array_equal(got, bt.unpack_np(fill, keys, meta))


def test_a_piece_is_a_view_of_the_file_s_members_but_for_the_end():
    """Nothing of the stream is copied on the host on its way to the
    device, except the zero-padded end."""
    fill, keys, meta = packed("0.5", 4096)
    _base, pieces = bt.packed_pieces(fill, keys, meta, 8192)
    pieces = list(pieces)
    assert len(pieces) > 3
    for _start, _lo, _hi, keys_piece, meta_piece in pieces[:-2]:
        assert np.shares_memory(keys_piece, keys)
        assert np.shares_memory(meta_piece, meta)
    start, lo, hi, keys_piece, meta_piece = pieces[-1]
    assert hi == 4096 and lo <= hi
    assert not keys_piece.reshape(-1)[4 * (keys.shape[0] - start):].any()
    assert not meta_piece.reshape(-1)[keys.shape[0] - start:].any()


def test_the_spreads_are_the_row_s_layout():
    """Lane ``5s + w`` takes key word ``4s + w``, lane ``5s + 4`` meta
    word ``s`` (worked out in NumPy at import; a spread whose lanes met
    would have raised there)."""
    lanes = np.arange(bt.ROW_WORDS)
    row = lanes.copy()
    for k, arrives in bt._KEY_SPREAD:
        row = np.where(arrives, np.roll(row, k), row)
    slot, word = np.divmod(np.arange(bt.SLOTS * 5), 5)
    assert np.array_equal(row[:120][word < 4], np.arange(96))
    row = lanes.copy()
    for k, arrives in bt._META_SPREAD:
        row = np.where(arrives, np.roll(row, k), row)
    assert np.array_equal(row[:120][word == 4], np.arange(bt.SLOTS) + 4)
    assert len(bt._KEY_SPREAD) == len(bt._META_SPREAD) == 5


# -- (2) save, then load on the device ----------------------------------------


def standing(n: int, bits: int = BITS):
    """An aggregator that folded serials ``0 .. n``."""
    agg = TpuAggregator(capacity=1 << bits, batch_size=256, grow_at=0.0)
    agg.registry.assign_issuer(Issuer.from_spki(b"an spki"))
    eh = agg._now_hour() + 1000
    res = ckptstate.fold_serials(agg, eh, n, 0)
    assert int(res.was_unknown.sum()) == n
    return agg, eh


def fingerprints_of(agg, eh: int, start: int, n: int) -> np.ndarray:
    from ct_mapreduce_tpu.core import packing

    return packing.fingerprints_np(
        np.zeros((n,), np.int64), np.full((n,), eh, np.int64),
        ckptstate._serials(start, n), np.full((n,), 16, np.int64))


@pytest.mark.parametrize("made_with_bits", [BITS, BITS + 2],
                         ids=["the-saved-shape", "another-shape"])
@pytest.mark.parametrize("n", [0, 200, 1500])
def test_a_saved_table_comes_back_on_the_device(tmp_path, n, made_with_bits):
    """``drain``, ``contains`` of every saved row and of rows never
    saved, the count, and a further ingest that repeats standing rows:
    they count as known. The constructor's table is the one restored
    where it has the base's shape, and let go where it has not."""
    saved, eh = standing(n)
    path = str(tmp_path / "agg.npz")
    saved.save_checkpoint(path)
    want = saved.drain()
    rows_saved = np.asarray(saved.table.rows)

    agg = TpuAggregator(capacity=1 << made_with_bits, batch_size=256,
                        grow_at=0.0)
    agg.load_checkpoint(path)
    assert isinstance(agg.table.rows, jax.Array)
    assert agg.capacity == saved.capacity == agg.table.capacity
    assert int(agg.table.count) == n and agg._table_fill == n
    assert np.array_equal(np.asarray(agg.table.rows), rows_saved)
    got = agg.drain()
    assert got.counts == want.counts and got.total == want.total == n
    assert agg._device_contains(fingerprints_of(agg, eh, 0, n)).all()
    assert not agg._device_contains(fingerprints_of(agg, eh, n, 64)).any()
    assert counters()["restore.host_unpacked"] == 0.0

    res = ckptstate.fold_serials(agg, eh, 256, max(0, n - 100))
    known = min(n, 100)
    assert int(res.was_unknown.sum()) == 256 - known
    assert not res.was_unknown[:known].any()
    assert agg.drain().total == n + 256 - known


def rewrite(path: str, **changed) -> None:
    with np.load(path, allow_pickle=True) as z:
        members = dict(z)
    members.update(changed)
    with open(path, "wb") as fh:
        np.savez(fh, **members)


@pytest.mark.parametrize("reader", [TpuAggregator, HostSnapshotAggregator])
@pytest.mark.parametrize("fault", ["a-row-short", "a-fill-of-25"])
def test_fills_that_do_not_add_up_raise_before_anything_is_put(
        tmp_path, monkeypatch, reader, fault):
    saved, _eh = standing(300)
    path = str(tmp_path / "agg.npz")
    saved.save_checkpoint(path)
    with np.load(path) as z:
        fill = z["fill"].copy()
    if fault == "a-row-short":
        fill[int(np.argmax(fill > 0))] -= 1
    else:
        # One bucket says 25; as many others say one row fewer.
        some = np.flatnonzero(fill > 0)
        fill[some[1:26 - int(fill[some[0]])]] -= 1
        fill[some[0]] = 25
        assert int(fill.sum()) == 300 and fill.max() == 25
    rewrite(path, fill=fill)
    agg = reader(capacity=1 << BITS, batch_size=256, grow_at=0.0)
    puts = []
    monkeypatch.setattr(jax, "device_put",
                        lambda *a, **k: puts.append(a) or pytest.fail("put"))
    with pytest.raises(ValueError, match="packed base"):
        agg.load_checkpoint(path)
    assert puts == []


def test_the_host_only_reader_builds_its_rows_in_numpy(tmp_path):
    """Residency decides the path: the report's reader holds NumPy
    arrays only, puts nothing on a device, and says so in the counter;
    its rows are the device reader's."""
    saved, _eh = standing(700)
    path = str(tmp_path / "agg.npz")
    saved.save_checkpoint(path)
    rows_saved = np.asarray(saved.table.rows)
    del saved
    gc.collect()
    before = len(jax.live_arrays())
    host = HostSnapshotAggregator(capacity=1 << BITS, batch_size=256,
                                  grow_at=0.0)
    trace.enable()
    host.load_checkpoint(path)
    assert type(host.table.rows) is np.ndarray
    assert type(host.table.count) is np.ndarray
    assert len(jax.live_arrays()) == before
    assert np.array_equal(host.table.rows, rows_saved)
    assert counters()["restore.host_unpacked"] == 1.0
    assert host.drain().total == 700
    (unpack,) = spans("restore.unpack")
    assert unpack["args"] == {"rows": 700, "buckets": 128, "where": "host"}
    assert spans("restore.put") == []


def test_one_table_is_live_after_a_restore(tmp_path):
    """The constructor's table is donated to the program and the packed
    pieces are freed with it: one table-sized buffer, and none of a
    piece's size, is left on the device."""
    bits = 13  # a shape no other test of this file makes
    saved, _eh = standing(3000, bits)
    path = str(tmp_path / "agg.npz")
    saved.save_checkpoint(path)
    shape = saved.table.rows.shape
    del saved
    gc.collect()

    def tables():
        return [a for a in jax.live_arrays() if a.shape == shape]

    piece = bt.unpack_piece_rows(shape[0])
    assert tables() == []
    agg = TpuAggregator(capacity=1 << bits, batch_size=256, grow_at=0.0)
    made = agg.table.rows
    assert len(tables()) == 1
    agg.load_checkpoint(path)
    assert made.is_deleted()
    del made
    gc.collect()
    assert len(tables()) == 1 and tables()[0] is agg.table.rows
    assert not [a for a in jax.live_arrays()
                if a.shape in (((piece + bt.UNPACK_HALO) // 32, 128),
                               ((piece + bt.UNPACK_HALO) // 128, 128))]
    assert int(agg.table.count) == 3000


def test_the_restore_s_spans_nest_and_say_what_they_moved(tmp_path):
    saved, _eh = standing(900)
    path = str(tmp_path / "agg.npz")
    saved.save_checkpoint(path)
    agg = TpuAggregator(capacity=1 << BITS, batch_size=256, grow_at=0.0)
    trace.enable()
    agg.load_checkpoint(path)
    by_name = {e["name"]: e for e in spans() if e["name"].startswith("restore.")}
    assert list(sorted(by_name)) == ["restore.base", "restore.put",
                                     "restore.read", "restore.unpack",
                                     "restore.verify"]
    base = by_name["restore.base"]
    import os

    assert base["args"] == {"rows": 900, "buckets": 128,
                            "bytes": os.path.getsize(path)}
    for child in ("verify", "read", "put", "unpack"):
        assert by_name["restore." + child]["parent"] == base["id"], child
    order = sorted(by_name.values(), key=lambda e: e["ts"])
    assert [e["name"] for e in order] == [
        "restore.base", "restore.verify", "restore.read", "restore.put",
        "restore.unpack"]
    piece = bt.unpack_piece_rows(128)
    assert by_name["restore.put"]["args"] == {
        "bytes": 128 + 128 * 4 + (piece + bt.UNPACK_HALO) * 20, "pieces": 1}
    assert by_name["restore.unpack"]["args"] == {
        "rows": 900, "buckets": 128, "where": "device"}
    assert counters()["restore.host_unpacked"] == 0.0


def test_a_restore_compiles_one_program_whatever_the_rows(tmp_path):
    """No compiled shape follows the row count: a second base of the
    same table at another load restores through the program the first
    compiled."""
    paths = []
    for n in (150, 1100):
        saved, _eh = standing(n)
        paths.append(str(tmp_path / f"agg{n}.npz"))
        saved.save_checkpoint(paths[-1])
    agg = TpuAggregator(capacity=1 << BITS, batch_size=256, grow_at=0.0)
    agg.load_checkpoint(paths[0])
    compiled = bt.unpack_rows._cache_size()
    agg.load_checkpoint(paths[1])
    assert bt.unpack_rows._cache_size() == compiled
    assert int(agg.table.count) == 1100
