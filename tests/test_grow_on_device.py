"""A table grows on the chip, with its programs ready.

``buckettable.grow_rehash`` doubles a bucket table where it lives: one
streaming split of the old rows (a row in its home bucket goes to one
of the two buckets the doubled table has for it), then an ordinary
insert of the rows that lay past a full bucket. The plain reference is
the host's: ``drain_np`` every row and ``bulk_insert_np`` them into a
fresh table of twice the buckets. Through the aggregator: a growth
keeps counts exact whatever is in flight, crosses nothing to the host,
and after ``prepare_growth`` compiles nothing.
"""

import datetime
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ct_mapreduce_tpu.agg import TpuAggregator
from ct_mapreduce_tpu.agg import aggregator as agg_mod
from ct_mapreduce_tpu.core import packing
from ct_mapreduce_tpu.ops import buckettable as bt
from ct_mapreduce_tpu.telemetry import metrics as tmetrics
from ct_mapreduce_tpu.telemetry import trace

from certgen import make_cert

UTC = datetime.timezone.utc
NOW = datetime.datetime(2024, 6, 1, tzinfo=UTC)
MIX = np.uint32(0x9E3779B9)


# -- the program against the reference --------------------------------------


def keys_at_home(rng, homes: np.ndarray, nb: int) -> np.ndarray:
    """Random fingerprints whose home bucket in a table of ``nb``
    buckets is ``homes`` (the bits above it are random, so the doubled
    table deals them to both halves)."""
    n = len(homes)
    keys = rng.integers(0, 1 << 32, size=(n, 4), dtype=np.uint64).astype(
        np.uint32)
    h = (keys[:, 0] & ~np.uint32(nb - 1)) | homes.astype(np.uint32)
    keys[:, 0] = h ^ (keys[:, 1] * MIX)
    return keys


def table_at(seed: int, nb: int, load: float, crowded=()):
    """``(rows, keys, meta)``: a host-built table of ``nb`` buckets at
    ``load``, its keys' homes uniform, plus ``crowded`` = (home, keys)
    pairs that overfill a bucket so that rows hop whatever the load."""
    rng = np.random.default_rng(seed)
    n = int(nb * bt.SLOTS * load)
    homes = [rng.integers(0, nb, size=n)]
    homes += [np.full(k, home) for home, k in crowded]
    keys = keys_at_home(rng, np.concatenate(homes), nb)
    assert len(np.unique(keys, axis=0)) == len(keys)
    meta = rng.integers(0, 1 << 32, size=len(keys), dtype=np.uint64).astype(
        np.uint32)
    rows = np.zeros((nb, bt.ROW_WORDS), np.uint32)
    assert bt.bulk_insert_np(rows, keys, meta) == 0
    return rows, keys, meta


def reference_double(rows: np.ndarray) -> np.ndarray:
    """The plain reference: every row drained on the host and placed in
    a fresh table of twice the buckets."""
    keys, meta = bt.drain_np(bt.BucketTable(rows, np.int32(0)))
    out = np.zeros((2 * rows.shape[0], bt.ROW_WORDS), np.uint32)
    assert bt.bulk_insert_np(out, keys, meta) == 0
    return out


def row_list(rows: np.ndarray) -> list[bytes]:
    slots = rows[:, : bt.SLOTS * 5].reshape(-1, 5)
    return sorted(bytes(s) for s in slots[slots[:, :4].any(-1)])


CASES = {
    # name: (seed, buckets, load, crowded homes)
    "load-0.3": (31, 64, 0.3, ((5, 30), (6, 24), (40, 49))),
    "load-0.69": (69, 128, 0.69, ()),
    "load-0.9": (90, 64, 0.9, ()),
    "chain-wraps-the-last-bucket": (63, 64, 0.5, ((63, 40), (0, 20))),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_grown_on_the_device_is_the_reference_grown_on_the_host(case):
    seed, nb, load, crowded = CASES[case]
    rows, keys, meta = table_at(seed, nb, load, crowded)
    fills = rows[:, bt.FILL_WORD]
    homes = ((keys[:, 0] ^ (keys[:, 1] * MIX)) & np.uint32(nb - 1))
    assert (fills == bt.SLOTS).any(), "no bucket full: nothing hopped"
    if case.startswith("chain-wraps"):
        assert np.bincount(homes, minlength=nb)[nb - 1] > bt.SLOTS
    state = bt.BucketTable(jnp.asarray(rows), jnp.asarray(np.int32(len(keys))))
    grown, rehomed, overflowed = bt.grow_rehash(state)
    got = np.asarray(grown.rows)
    want = reference_double(rows)
    assert got.shape == want.shape == (2 * nb, bt.ROW_WORDS)
    assert int(overflowed) == 0 and int(rehomed) > 0
    # The same set of (key, meta) rows, none twice, none lost.
    assert row_list(got) == row_list(want) == row_list(rows)
    assert len(set(row_list(got))) == len(keys) == int(grown.count)
    # Fill words right, slots contiguous, spare words clean.
    occ = got[:, : bt.SLOTS * 5].reshape(2 * nb, bt.SLOTS, 5)[
        :, :, :4].any(-1)
    assert (occ.sum(1) == got[:, bt.FILL_WORD]).all()
    assert (occ[:, :-1] >= occ[:, 1:]).all()
    assert not got[:, bt.FILL_WORD + 1:].any()
    # Every key is found, on the device and by the host's reader; the
    # old table is untouched (it is the live one until the new stands).
    assert np.asarray(bt.contains(grown, jnp.asarray(keys))).all()
    assert bt.contains_np(got, keys).all()
    assert (np.asarray(state.rows) == rows).all()
    # An insert of known and new keys answers as the reference does.
    rng = np.random.default_rng(7)
    fresh = keys_at_home(rng, rng.integers(0, nb, size=96), nb)
    batch = np.concatenate([keys[::3][:96], fresh, fresh[:8]])
    bmeta = np.arange(len(batch), dtype=np.uint32)
    valid = np.ones(len(batch), bool)
    ref_state = bt.BucketTable(jnp.asarray(want),
                               jnp.asarray(np.int32(len(keys))))
    a, a_new, a_ovf = bt.insert(grown, jnp.asarray(batch),
                                jnp.asarray(bmeta), jnp.asarray(valid))
    b, b_new, b_ovf = bt.insert(ref_state, jnp.asarray(batch),
                                jnp.asarray(bmeta), jnp.asarray(valid))
    assert (np.asarray(a_new) == np.asarray(b_new)).all()
    assert np.asarray(a_new).sum() == 96
    assert not np.asarray(a_ovf).any() and not np.asarray(b_ovf).any()
    assert row_list(np.asarray(a.rows)) == row_list(np.asarray(b.rows))
    assert int(a.count) == int(b.count) == len(keys) + 96


# -- the split against its restatement, word for word ------------------------


def keys_hashing_to(rng, h: np.ndarray) -> np.ndarray:
    """Random fingerprints whose hash is ``h``, every bit of it."""
    keys = rng.integers(0, 1 << 32, size=(len(h), 4), dtype=np.uint64).astype(
        np.uint32)
    keys[:, 0] = h.astype(np.uint32) ^ (keys[:, 1] * MIX)
    return keys


def put(rows: np.ndarray, bucket: int, keys: np.ndarray, rng) -> None:
    """``keys`` into the next free slots of ``bucket``, as an insert
    would leave them (contiguous, fill word kept)."""
    fill = int(rows[bucket, bt.FILL_WORD])
    assert fill + len(keys) <= bt.SLOTS
    slots = rows[:, : bt.SLOTS * 5].reshape(-1, bt.SLOTS, 5)
    slots[bucket, fill:fill + len(keys), :4] = keys
    slots[bucket, fill:fill + len(keys), 4] = rng.integers(
        0, 1 << 32, size=len(keys), dtype=np.uint64).astype(np.uint32)
    rows[bucket, bt.FILL_WORD] = fill + len(keys)


def corner_table(corner: str, nb: int = 16) -> np.ndarray:
    """A hand-built table around one bucket (5) in a given state, the
    buckets around it ordinary."""
    rng = np.random.default_rng(len(corner))
    rows = np.zeros((nb, bt.ROW_WORDS), np.uint32)
    above = rng.integers(0, 1 << 20, size=bt.SLOTS).astype(np.uint32) * (
        2 * nb)  # the bits over the doubled table's
    for b in (2, 3, 9):
        put(rows, b, keys_at_home(rng, np.full(7, b), nb), rng)
    if corner == "an-empty-bucket":
        assert not rows[5].any() and not rows[0].any()
    elif corner == "a-bucket-all-lo":
        put(rows, 5, keys_hashing_to(rng, above + 5), rng)
    elif corner == "a-bucket-all-hi":
        put(rows, 5, keys_hashing_to(rng, above + nb + 5), rng)
    elif corner == "lo-and-hi-interleaved":
        h = above + 5 + nb * (np.arange(bt.SLOTS) % 2)
        put(rows, 5, keys_hashing_to(rng, h), rng)
    elif corner == "a-full-bucket-all-past-home":
        # Bucket 4 is full of its own, so 24 more rows of home 4 hopped
        # into bucket 5 and fill it; bucket 5's own row lies in 6.
        put(rows, 4, keys_at_home(rng, np.full(bt.SLOTS, 4), nb), rng)
        put(rows, 5, keys_at_home(rng, np.full(bt.SLOTS, 4), nb), rng)
        put(rows, 6, keys_at_home(rng, np.array([5, 6, 6]), nb), rng)
    elif corner == "home-and-past-home-interleaved":
        homes = np.where(np.arange(20) % 3 == 1, 4, 5)
        put(rows, 5, keys_at_home(rng, homes, nb), rng)
    elif corner == "past-home-wraps-to-bucket-0":
        put(rows, 0, keys_at_home(rng, np.array([nb - 1, 0, nb - 1]), nb),
            rng)
    else:
        raise AssertionError(corner)
    return rows


def split_np(rows: np.ndarray):
    """The split restated: ``(new rows, past, rows left behind)``. A
    bucket's 24 slots in order; a slot that holds a row whose hash
    names this bucket is dealt to ``b`` or ``b + nb`` by the hash's
    next bit, behind the slots dealt there before it; any other
    occupied slot is left behind (in bucket-then-slot order)."""
    nb = rows.shape[0]
    new = np.zeros((2 * nb, bt.ROW_WORDS), np.uint32)
    past = np.zeros(nb, np.int32)
    behind = []
    for b in range(nb):
        for s in range(bt.SLOTS):
            slot = rows[b, 5 * s:5 * s + 5]
            if not slot[:4].any():
                continue
            h = int(slot[0]) ^ (int(slot[1]) * int(MIX) & 0xFFFFFFFF)
            if h & (nb - 1) != b:
                past[b] += 1
                behind.append(slot)
                continue
            to = b + (h & nb)
            n = int(new[to, bt.FILL_WORD])
            new[to, 5 * n:5 * n + 5] = slot
            new[to, bt.FILL_WORD] = n + 1
    return new, past, np.array(behind, np.uint32).reshape(-1, 5)


SPLIT_CASES = {
    # name: (how the table is made, SPLIT_BLOCK or None for the default)
    "load-0.1": (lambda: table_at(10, 64, 0.1, ((7, 30),))[0], None),
    "load-0.5": (lambda: table_at(50, 64, 0.5)[0], None),
    "load-0.69": (lambda: table_at(69, 128, 0.69)[0], None),
    "load-0.69-in-eight-blocks": (lambda: table_at(69, 128, 0.69)[0], 16),
    "load-0.9-in-four-blocks": (lambda: table_at(90, 64, 0.9)[0], 16),
    "crowded-buckets": (
        lambda: table_at(31, 64, 0.3, ((5, 30), (6, 24), (40, 49)))[0], None),
    "crowded-buckets-in-two-blocks": (
        lambda: table_at(31, 64, 0.3, ((5, 30), (6, 24), (40, 49)))[0], 32),
    "one-bucket": (lambda: table_at(1, 1, 0.5)[0], None),
    "fewer-buckets-than-a-tile": (lambda: table_at(4, 4, 0.6)[0], None),
}
for _corner in ("an-empty-bucket", "a-bucket-all-lo", "a-bucket-all-hi",
                "lo-and-hi-interleaved", "a-full-bucket-all-past-home",
                "home-and-past-home-interleaved",
                "past-home-wraps-to-bucket-0"):
    SPLIT_CASES[_corner] = (
        lambda corner=_corner: corner_table(corner), None)
SPLIT_CASES["a-full-bucket-all-past-home-across-blocks"] = (
    lambda: corner_table("a-full-bucket-all-past-home"), 8)


@pytest.mark.parametrize("case", sorted(SPLIT_CASES))
def test_the_split_is_its_restatement_word_for_word(case, monkeypatch):
    """``split_rows`` against :func:`split_np`: the doubled table's
    rows (slot order kept within each half, fill word, every spare
    word zero) and ``past``; then ``past_home_chunk`` against the rows
    the restatement left behind, in its order, a chunk of 16 at a time
    (so a bucket's run of such rows is cut by a chunk's end)."""
    make, block = SPLIT_CASES[case]
    rows = make()
    nb = rows.shape[0]
    if block is not None:
        assert nb > block
        monkeypatch.setattr(bt, "SPLIT_BLOCK", block)
        monkeypatch.setattr(bt, "SPLIT_TILE", 8)
    else:
        assert nb <= bt.SPLIT_BLOCK
    want, want_past, behind = split_np(rows)
    new_rows, past = jax.jit(bt.split_rows)(jnp.asarray(rows))
    got = np.asarray(new_rows)
    assert got.shape == (2 * nb, bt.ROW_WORDS) and got.dtype == np.uint32
    assert (np.asarray(past) == want_past).all()
    assert (got == want).all(), np.argwhere(got != want)[:4]
    assert not got[:, bt.FILL_WORD + 1:].any()
    if "past-home" in case:
        assert len(behind) >= 2
    if case == "a-full-bucket-all-past-home":
        assert want_past[5] == bt.SLOTS and not want[5].any() \
            and not want[5 + nb].any()
    chunk = 16
    index = bt._running_index(past)
    assert int(index[-1][-1]) == len(behind)
    fetch = jax.jit(bt.past_home_chunk, static_argnames=("chunk",))
    for start in range(0, len(behind) + 1, chunk):
        keys, meta, valid = (np.asarray(a) for a in fetch(
            jnp.asarray(rows), index, jnp.int32(start), chunk=chunk))
        n = min(chunk, len(behind) - start)
        assert valid.tolist() == [True] * n + [False] * (chunk - n)
        assert (keys[:n] == behind[start:start + n, :4]).all()
        assert (meta[:n] == behind[start:start + n, 4]).all()


def test_the_split_takes_a_table_a_block_at_a_time(monkeypatch):
    """More buckets than a block: the loop's slices land where a whole
    pass would put them."""
    monkeypatch.setattr(bt, "SPLIT_BLOCK", 16)
    monkeypatch.setattr(bt, "SPLIT_TILE", 8)
    rows, keys, _meta = table_at(11, 64, 0.8)
    new_rows, past = jax.jit(bt.split_rows)(jnp.asarray(rows))
    got = np.asarray(new_rows)
    homes = (keys[:, 0] ^ (keys[:, 1] * MIX)) & np.uint32(127)
    found = bt.contains_np(got, keys)
    # What the split left behind is what lay past its home, and what it
    # placed lies in its new home.
    assert int(np.asarray(past).sum()) == int((~found).sum()) > 0
    slots = got[:, : bt.SLOTS * 5].reshape(128, bt.SLOTS, 5)
    b, s = np.nonzero(slots[:, :, :4].any(-1))
    k = slots[b, s]
    assert (((k[:, 0] ^ (k[:, 1] * MIX)) & np.uint32(127)) == b).all()
    assert len(b) == int(found.sum()) and set(homes[found]) <= set(b)


def test_rows_that_find_no_room_leave_the_old_table_alive():
    """A doubling whose re-homed rows overflow says so and donates
    nothing: the caller keeps the table it had."""
    rows, keys, _meta = table_at(3, 64, 0.3, ((9, 24 * 6),))
    state = bt.BucketTable(jnp.asarray(rows), jnp.asarray(np.int32(len(keys))))
    _grown, rehomed, overflowed = bt.grow_rehash(state, max_probes=1)
    assert int(rehomed) >= 24 * 5 and int(overflowed) > 0
    assert (np.asarray(state.rows) == rows).all()


# -- through the aggregator --------------------------------------------------


def leaf(serial, issuer_cn="Split CA"):
    return make_cert(serial=serial, issuer_cn=issuer_cn, is_ca=False,
                     subject_cn=f"s{serial}.example.com")


@pytest.fixture(scope="module")
def certs():
    ca = make_cert(issuer_cn="Split CA")
    return ca, [leaf(40_000 + i) for i in range(640)]


@pytest.fixture
def sink():
    s = tmetrics.InMemSink()
    tmetrics.set_sink(s)
    yield s
    tmetrics.set_sink(tmetrics.InMemSink())


class Compiles:
    """Every program XLA compiles (or reads from a cache) while the
    block runs: the listener the benchmark's harness counts by."""

    def __init__(self):
        self.n = 0

    def __call__(self, event: str, _seconds: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *_exc):
        from jax._src import monitoring

        monitoring.unregister_event_duration_listener(self)


def packed(a: TpuAggregator, ca: bytes, ders: list[bytes]):
    idx = a.registry.get_or_assign(ca)
    return packing.pack_entries([(d, idx) for d in ders])


def submit(a: TpuAggregator, ca: bytes, ders: list[bytes]):
    b = packed(a, ca, ders)
    return a.ingest_packed_submit(b.data, b.length, b.issuer_idx, b.valid)


def counters(sink) -> dict:
    return sink.snapshot()["counters"]


def test_growth_with_dispatches_outstanding_keeps_counts_exact(certs, sink):
    ca, ders = certs
    a = TpuAggregator(capacity=384, batch_size=64, now=NOW, grow_at=0.6,
                      max_capacity=1 << 13)
    assert a.capacity == 384
    pending = [submit(a, ca, ders[i:i + 64]) for i in range(0, 192, 64)]
    assert len(a._outstanding) == 3 and a.capacity == 384
    # 192 in flight + 64 coming > 0.6 x 384: this submit grows first,
    # and must fold the three before it.
    pending.append(submit(a, ca, ders[192:256]))
    assert a.capacity == 768 and len(a._outstanding) == 1
    for p in pending:
        assert p.complete().was_unknown.all()
    for i in range(0, 256, 64):  # (a whole 256 coming would grow again)
        assert not submit(a, ca, ders[i:i + 64]).complete().was_unknown.any()
    assert a.drain().total == 256
    assert a.metrics["host_lane"] == a.metrics["overflow"] == 0
    got = counters(sink)
    assert got["aggregator.table_grow"] == 1
    assert got["grow.host_bytes"] == 0  # no table row crossed to the host
    assert got["grow.unprepared"] == 1  # nobody made its programs ready
    assert got["grow.rehomed_rows"] >= 0
    gauges = sink.snapshot()["gauges"]
    assert gauges["aggregator.table_slots"] == 768
    assert gauges["aggregator.table_load"] == pytest.approx(256 / 768)


def test_after_prepare_a_growth_its_step_and_its_save_compile_nothing(
        certs, sink, tmp_path):
    ca, ders = certs
    trace.enable(ring_size=1 << 12)
    try:
        a = TpuAggregator(capacity=384, batch_size=64, now=NOW, grow_at=0.7,
                          max_capacity=1 << 13)
        path = str(tmp_path / "agg.npz")
        submit(a, ca, ders[:128]).complete()
        a.save_checkpoint(path)
        assert a.prepare_growth() is False  # load 0.33: below the mark
        submit(a, ca, ders[128:256]).complete()
        a.save_checkpoint(path)
        # Load 0.667 > 15/16 x 0.7: the round's end makes ready.
        assert 256 > agg_mod.GROW_PREPARE_AT * 0.7 * 384
        assert a.prepare_growth() is True
        assert a.prepare_growth() is False  # once a capacity
        assert a.capacity == 384 and a.drain().total == 256
        with Compiles() as compiles:
            res = submit(a, ca, ders[256:320]).complete()  # grows first
            assert a.capacity == 768
            assert submit(a, ca, ders[320:384]).complete().was_unknown.all()
            a.save_checkpoint(path)
        assert compiles.n == 0
        assert res.was_unknown.all()
        got = counters(sink)
        assert got["aggregator.table_grow"] == 1
        assert got["grow.unprepared"] == 0 and got["grow.host_bytes"] == 0
        spans = {e["name"]: e for e in trace.get_tracer().events()
                 if e.get("ph") == "X" and e["name"].startswith("grow.")}
        # The checkpoint after the growth restores, at the grown size.
        b = TpuAggregator(capacity=384, batch_size=64, now=NOW, grow_at=0.7,
                          max_capacity=1 << 13)
        b.load_checkpoint(path)
        assert b.capacity == 768 and b.drain().total == 384
        assert not submit(b, ca, ders[:64]).complete().was_unknown.any()
        assert submit(b, ca, ders[384:448]).complete().was_unknown.all()
        # The spans of the family, with their arguments.
        assert set(spans) == {"grow.prepare", "grow.table",
                              "grow.wait_outstanding", "grow.rehash"}
        assert spans["grow.prepare"]["args"] == {
            "from_slots": 384, "to_slots": 768, "programs": 4}
        assert spans["grow.table"]["args"] == {
            "from_slots": 384, "to_slots": 768, "rows": 256}
        assert spans["grow.rehash"]["args"]["rows"] == 256
        # Which of the split kernel's two code paths ran: off the chip,
        # the interpreted one.
        assert spans["grow.rehash"]["args"]["split"] == "interpret"
        assert spans["grow.rehash"]["args"]["rehomed"] \
            == got["grow.rehomed_rows"]
        assert spans["grow.rehash"]["parent"] == spans["grow.table"]["id"]
        assert spans["grow.wait_outstanding"]["parent"] \
            == spans["grow.table"]["id"]
    finally:
        trace.disable()


def test_an_explicit_growth_doubles_as_often_as_it_takes(certs, sink):
    ca, ders = certs
    a = TpuAggregator(capacity=384, batch_size=64, now=NOW)
    submit(a, ca, ders[:128]).complete()
    a.grow(1 << 11)  # 384 -> 768 -> 1536 -> 3072
    assert a.capacity == 3072
    assert not submit(a, ca, ders[:128]).complete().was_unknown.any()
    assert a.drain().total == 128
    got = counters(sink)
    assert got["aggregator.table_grow"] == 1
    assert got["grow.unprepared"] == 3 and got["grow.host_bytes"] == 0


def test_the_open_layout_still_grows_through_the_host(certs, sink,
                                                      monkeypatch):
    monkeypatch.setenv("CTMR_TABLE", "open")
    ca, ders = certs
    a = TpuAggregator(capacity=256, batch_size=64, now=NOW, grow_at=0.6,
                      max_capacity=1 << 12)
    assert a.prepare_growth() is False
    for i in range(0, 256, 64):
        assert submit(a, ca, ders[i:i + 64]).complete().was_unknown.all()
    assert a.capacity == 512 and a.drain().total == 256
    got = counters(sink)
    assert got["grow.host_bytes"] == 256 * 5 * 4  # the table it drained
    assert got["grow.unprepared"] == 1


def test_ct_fetch_prepares_between_rounds():
    """The round's end calls it after the save and before ``idle``."""
    import inspect

    from ct_mapreduce_tpu.cmd import ct_fetch

    src = inspect.getsource(ct_fetch.main)
    save = src.index("model.save()")
    assert save < src.index("model.prepare_growth()") \
        < src.index('run_stage["stage"] = "idle"')
    assert not [k for k in os.environ if k.startswith("CTMR_GROW")]
