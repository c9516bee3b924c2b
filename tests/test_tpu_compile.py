"""The main path's kernels compile for the chip — asked of the TPU
compiler installed here, for a v5e that is described and not attached
— plus the two start-up checks that keep a chip run from finishing on
the CPU. Nothing here runs on a device; ``python chip_smoke.py`` (on
the chip) is what runs.

The topology is described inside a module-scoped fixture, so only the
worker that is handed this file loads the TPU library.
"""

import os

import numpy as np
import pytest


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("lanes", [65536, 1048576])
def test_pallas_sha_kernel_compiles_for_v5e(one_chip, lanes):
    """The Mosaic SHA-256 kernel at the CLI's batch width and at the
    2^20-lane width the July records used."""
    import jax
    import jax.numpy as jnp

    from ct_mapreduce_tpu.ops import pallas_sha256

    block = jax.ShapeDtypeStruct((lanes, 16), jnp.uint32, sharding=one_chip)
    compiled = jax.jit(
        pallas_sha256.sha256_fingerprint64_pallas).lower(block).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_contains_compiles_for_v5e_at_serve_width(one_chip):
    """The query plane's membership probe at one of its pow2-padded
    widths, against the 2^26-slot table chip_smoke.py keeps resident."""
    import jax
    import jax.numpy as jnp

    from ct_mapreduce_tpu.ops import buckettable

    shape = jax.eval_shape(lambda: buckettable.make_table(1 << 26))
    table = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        shape)
    keys = jax.ShapeDtypeStruct((4096, 4), jnp.uint32, sharding=one_chip)
    compiled = buckettable.contains.lower(table, keys).compile()
    mem = compiled.memory_analysis()
    # The table is an argument, never copied: 2^22 bucket rows × 512 B.
    assert mem.argument_size_in_bytes >= (1 << 22) * 512
    assert mem.temp_size_in_bytes < 1 << 30


def test_a_full_saves_packing_compiles_for_v5e_without_a_table_sized_temporary(
        one_chip, topo):
    """The two programs a full save dispatches under the table lock
    (PR 42), at the 2^26-slot table: the index reads the fill column
    with no temporary at all (a recount from the key words asked the
    v5e compiler for 2 GB of lane-shifted flags), a chunk's temporary
    is its three 128-word gathers, and a chunk is 5 x 262,144 words, flat.
    On the four-chip host the same two run a shard a chip."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ct_mapreduce_tpu.ops import buckettable

    def compiled(fn, *shapes):
        return jax.jit(fn).lower(*shapes).compile()

    def shaped(tree, sharding):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=sharding), tree)

    rows = jax.ShapeDtypeStruct((1 << 22, 128), jnp.uint32, sharding=one_chip)
    index_mem = compiled(buckettable.pack_index, rows).memory_analysis()
    assert index_mem.temp_size_in_bytes < 64 << 20
    _fill, index = jax.eval_shape(buckettable.pack_index, rows)
    assert [a.shape for a in index] == [(32768, 128), (256, 128), (256,)]
    chunk = buckettable.pack_chunk_rows(1 << 22)
    assert chunk == 1 << 18
    start = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    chunk_mem = compiled(
        functools.partial(buckettable.pack_chunk, chunk=chunk),
        rows, shaped(index, one_chip), start).memory_analysis()
    assert chunk_mem.temp_size_in_bytes < 512 << 20
    assert chunk_mem.output_size_in_bytes == 5 * chunk * 4  # 20 B a row

    # shard:4, as ShardedDedup.pack_programs builds them.
    from jax import shard_map

    mesh = Mesh(np.array(topo.devices[:4]), ("shard",))
    split = NamedSharding(mesh, P("shard"))
    rows4 = jax.ShapeDtypeStruct((1 << 22, 128), jnp.uint32, sharding=split)
    local = jax.ShapeDtypeStruct((1 << 20, 128), jnp.uint32)
    specs = jax.tree.map(lambda _: P("shard"),
                         jax.eval_shape(buckettable.pack_index, local))
    mapped = shard_map(buckettable.pack_index, mesh=mesh,
                       in_specs=P("shard"), out_specs=specs, check_vma=False)
    on_four = compiled(mapped, rows4)
    assert on_four.memory_analysis().temp_size_in_bytes < 64 << 20
    text = on_four.as_text()
    assert "all-gather" not in text and "all-reduce" not in text


def test_a_growths_split_compiles_for_v5e_a_block_at_a_time(
        one_chip, monkeypatch):
    """The two parts of ``buckettable.grow_rehash`` that are its own,
    at the 2^26-slot table (the third, the ordinary insert, is the
    step's and compiles for minutes): the split is a Pallas kernel that
    LOWERS for the chip (Mosaic; off a chip the program interprets it,
    so the test steers that here) and takes the old rows a block of
    buckets at a time, so it has no temporary outside the kernel and
    its output is the doubled table and a count a bucket; a chunk of
    the rows that lay past a full bucket is one 128-word gather. The
    whole program lowers under the name the benchmark's readers find
    it by."""
    import functools

    import jax
    import jax.numpy as jnp

    from ct_mapreduce_tpu.ops import buckettable

    def shaped(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    monkeypatch.setattr(buckettable, "split_runs_compiled", lambda: True)
    nb = 1 << 22
    rows = jax.ShapeDtypeStruct((nb, 128), jnp.uint32, sharding=one_chip)
    split = jax.jit(buckettable.split_rows).lower(rows).compile()
    assert "tpu_custom_call" in split.as_text()
    mem = split.memory_analysis()
    assert mem.output_size_in_bytes >= 2 * nb * 512 + nb * 4
    assert mem.temp_size_in_bytes < 64 << 20
    new, past = jax.eval_shape(buckettable.split_rows, rows)
    assert new.shape == (2 * nb, 128) and past.shape == (nb,)
    index = jax.eval_shape(buckettable._running_index, past)
    chunk = jax.jit(functools.partial(
        buckettable.past_home_chunk, chunk=buckettable.REHOME_CHUNK)).lower(
            rows, shaped(index),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    assert chunk.memory_analysis().temp_size_in_bytes < 1 << 30
    keys, meta, valid = jax.eval_shape(
        functools.partial(buckettable.past_home_chunk,
                          chunk=buckettable.REHOME_CHUNK),
        rows, index, jax.ShapeDtypeStruct((), jnp.int32))
    assert keys.shape == (65536, 4) and meta.shape == valid.shape == (65536,)
    state = buckettable.BucketTable(
        rows, jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip))
    lowered = buckettable.grow_rehash.lower(state).as_text()
    assert "module @jit_grow_rehash" in lowered
    assert "tpu_custom_call" in lowered


@pytest.mark.parametrize("bits", [22, 23])
def test_a_restore_s_unpack_compiles_for_v5e_into_the_table_it_is_given(
        one_chip, bits):
    """``buckettable.unpack_rows`` at the two restoring cells' tables
    (2^22 and 2^23 buckets: ``backfill-1log-growing``,
    ``backfill-1log-loaded``): the table is aliased to the output (no
    second one), a piece costs its own bytes on the device (20 B a
    packed row: no small minor dimension), the loop over blocks of
    buckets has no temporary of the table's size, and the program
    lowers under the name ``docs/METRICS.md`` gives it."""
    import jax
    import jax.numpy as jnp

    from ct_mapreduce_tpu.ops import buckettable

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    nb = 1 << bits
    piece = buckettable.unpack_piece_rows(nb)
    assert piece == 1 << 22
    span = piece + buckettable.UNPACK_HALO
    scalar = shaped((), jnp.int32)
    args = (shaped((nb, 128), jnp.uint32), shaped((nb,), jnp.uint8),
            shaped((nb,), jnp.int32), shaped((span // 32, 128), jnp.uint32),
            shaped((span // 128, 128), jnp.uint32), scalar, scalar, scalar)
    lowered = buckettable.unpack_rows.lower(
        *args, block=buckettable.UNPACK_BLOCK)
    assert "module @jit_unpack_rows" in lowered.as_text()
    mem = lowered.compile().memory_analysis()
    assert mem.alias_size_in_bytes == mem.output_size_in_bytes == nb * 512
    assert mem.argument_size_in_bytes - nb * 512 <= span * 20 + nb * 5 + (64 << 10)
    assert mem.temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("width", [16, 4096])
def test_the_mesh_probe_compiles_for_a_v5e_2x2_with_no_collective(topo, width):
    """The query plane's probe of a table sharded over four chips at
    2^26 slots a chip (``loglist3-serve-shard4``): one program over the
    mesh a width, every shard's block an argument on its own chip and
    never copied, nothing crossing chips."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ct_mapreduce_tpu.agg import sharded

    mesh = Mesh(np.array(topo.devices[:4]), (sharded.AXIS,))
    split = NamedSharding(mesh, P(sharded.AXIS))
    rows = jax.ShapeDtypeStruct((4 << 22, 128), jnp.uint32, sharding=split)
    keys = jax.ShapeDtypeStruct((4, width, 4), jnp.uint32, sharding=split)
    fn = sharded._shard_contains_program(mesh, sharded.AXIS, "bucket", 32)
    compiled = fn.lower(rows, keys).compile()
    mem = compiled.memory_analysis()  # a chip's
    assert mem.argument_size_in_bytes >= (1 << 22) * 512
    assert mem.temp_size_in_bytes < 64 << 20
    text = compiled.as_text()
    assert not any(op in text for op in (
        "all-gather", "all-reduce", "all-to-all", "collective-permute"))
    assert jax.eval_shape(fn, rows, keys).shape == (4, width)


def _tpu_ini(tmp_path, log_url="https://ct.example.com/none"):
    ini = tmp_path / "ct.ini"
    ini.write_text(
        f"logList = {log_url}\n"
        "backend = tpu\n"
        "batchSize = 64\n"
        "tableBits = 8\n"
        "healthAddr = \n"
    )
    return ini


def test_ct_fetch_names_its_device(tmp_path, monkeypatch, capsys):
    """``backend = tpu`` prints one line naming platform, device kind
    and count before any work (here: the CPU the tests ask for)."""
    import jax

    from ct_mapreduce_tpu.cmd import ct_fetch
    from ct_mapreduce_tpu.ingest import ctclient

    monkeypatch.setattr(
        ctclient, "_default_transport",
        lambda url: (200, {}, b'{"tree_size": 0, "timestamp": 0}'))
    ct_fetch.main(["-config", str(_tpu_ini(tmp_path)), "-nobars"])
    dev = jax.devices()[0]
    assert (f"device: platform=cpu kind={dev.device_kind} "
            f"count={len(jax.devices())}") in capsys.readouterr().err


def test_cpu_nobody_asked_for_is_refused(tmp_path, monkeypatch, capsys):
    """JAX's own CPU fallback (``JAX_PLATFORMS`` unset) must not carry
    a ``backend = tpu`` run to exit code 0, and chip_smoke.py refuses
    anything but a TPU whatever the environment says."""
    import chip_smoke
    from ct_mapreduce_tpu.cmd import ct_fetch

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert ct_fetch.main(
        ["-config", str(_tpu_ini(tmp_path)), "-nobars"]) == 2
    assert "fell back to the CPU" in capsys.readouterr().err
    with pytest.raises(SystemExit) as refused:
        chip_smoke.require_tpu(1)
    assert refused.value.code not in (0, None)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with pytest.raises(SystemExit):
        chip_smoke.require_tpu(1)
