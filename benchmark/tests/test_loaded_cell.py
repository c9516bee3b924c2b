"""Tests of the cell ``backfill-1log-loaded`` and of what the harness
learned for it: the standing table of ``prefill.py`` as the program
reads it, the fixture's arithmetic with a ``table_prefill`` block, the
two comparisons that join the eight, the controls, and what
``BENCHMARK.json`` lists. By hand, on the CPU:

  python3 -m pytest benchmark/tests/test_loaded_cell.py -q

Tier-1 runs them through ``test_benchmark.py``, which takes every name
of this file for its own when ``tests/`` imports it. The rehearsals
therefore make a checkout of their own to run in (the harness keeps a
run's state in ``<checkout>/.bench_work`` and the standing table in
``<checkout>/.bench_cache/prefill``, one a checkout).
"""

from __future__ import annotations

import base64
import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import fixture as fx  # noqa: E402
import harness  # noqa: E402
import layers  # noqa: E402
import listing  # noqa: E402
import prefill  # noqa: E402

CELL = "backfill-1log-loaded"
CONFIG = "icarus-dedup-loaded-1chip"
BLOCK = {"slots_log2": 12, "load": 0.5, "known_share": 0.5}
SPEC = dict(logs=1, page=64, dup_share=0.05,
            leaf_mix={"rsa2048": 0.7, "ec_p256": 0.3}, issuers=16,
            zipf_s=1.1, warmup_entries=256, window_entries=512)
# The cell's own metrics, in the order BENCHMARK.json lists them, and
# the older ones whose ``workloads`` name it too.
OWN = ("loaded.restore_s", "loaded.table_load_pct", "loaded.full_saves",
       "loaded.save_rows_m", "loaded.ckpt_save_s", "loaded.ckpt_d2h_s",
       "loaded.ckpt_write_s", "loaded.sink_starved_share",
       "loaded.fetch_blocked_share")
JOINED = ("loadgen.headroom_x", "fetch.us_per_entry", "decode.ns_per_entry",
          "h2d.ms_per_batch", "step.device_ns_per_entry", "sha256_roofline",
          "fold.us_per_entry", "ckpt.drain_s", "compile.programs",
          "device.idle_pct", "device.peak_hbm_gb", "ckpt.unpacked_saves")


def standing_12() -> tuple[prefill.Standing, fx.Templates, int]:
    tpl = fx.Templates()
    return (prefill.Standing.of(BLOCK, 16, 1.1), tpl,
            prefill.exp_hour_of(tpl.not_after))


def standing_certs(standing, tpl) -> list[tuple[bytes, bytes]]:
    """Every standing row as the certificate a log would deliver for
    it, with its issuer's: ``(leaf DER, CA DER)`` in row order."""
    with open(os.path.join(BENCH, "fixtures", "templates.json")) as fh:
        doc = json.load(fh)
    issuer = standing.issuer_of(np.arange(standing.rows)).tolist()
    out = []
    for j, k in enumerate(issuer):
        spec = doc["issuers"][k]["leaves"]["rsa2048"]
        der, off = base64.b64decode(spec["der"]), int(spec["serial_off"])
        out.append((der[:off + 1]
                    + (prefill.SERIAL_BASE + j).to_bytes(15, "big")
                    + der[off + tpl.serial_len:],
                    base64.b64decode(doc["issuers"][k]["issuer_der"])))
    return out


@pytest.fixture
def suite_devices(monkeypatch):
    """For the tests that run the program in this process. Tier-1's
    fixture for the rehearsals takes the suite's eight virtual devices
    out of ``XLA_FLAGS``; a JAX backend first touched under it would
    keep one device for the rest of the worker's life, and the mesh
    tests that the worker runs later would find no mesh. So the backend
    is made here, with the flag back in place."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        monkeypatch.setenv("XLA_FLAGS", (
            flags + " --xla_force_host_platform_device_count=8").strip())
    import jax

    jax.devices()


def aggregator():
    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator

    return TpuAggregator(capacity=1 << BLOCK["slots_log2"], batch_size=1024,
                         grow_at=0.7, max_capacity=1 << 28)


def base_rows(path: str) -> tuple[set, np.ndarray, dict]:
    z = np.load(path, allow_pickle=True)
    rows = {(*k, m) for k, m in zip(z["keys"].tolist(), z["meta"].tolist())}
    return rows, z["fill"], z


def test_the_program_restores_the_fixtures_base_and_knows_every_row(
        tmp_path, suite_devices):
    """(a) At 2^12 slots and load 0.5: the base ``prefill.py`` writes,
    loaded by ``TpuAggregator.load_checkpoint`` through its manifest,
    answers known for the certificate of every standing row and unknown
    for 1,000 serials outside it, and ``storage-statistics -json`` over
    it reports the fixture's per-issuer counts."""
    standing, tpl, hour = standing_12()
    assert prefill.table_slots(12) == 6144 and standing.rows == 3072
    path, built_s = prefill.ensure(str(tmp_path), standing, tpl.issuer_ids,
                                   hour)
    assert built_s > 0.0
    assert prefill.ensure(str(tmp_path), standing, tpl.issuer_ids, hour) \
        == (path, 0.0)  # the second run of a checkout finds it
    agg = aggregator()
    assert agg.capacity == prefill.table_slots(12)
    agg.load_checkpoint(path)
    assert int(np.asarray(agg.table.count)) == standing.rows
    assert agg._ckpt_chain_len == 0 and agg._ckpt_track  # armed on the manifest
    certs = standing_certs(standing, tpl)
    for lo in range(0, standing.rows, 1024):
        res = agg.ingest(certs[lo:lo + 1024])
        assert not np.asarray(res.was_unknown)[:1024].any()
    assert int(np.asarray(agg.table.count)) == standing.rows
    log = fx.LogFixture(fx.LogSpec(**dict(SPEC, warmup_entries=1024)), 3, 0)
    doc = json.loads(log.page_body(tpl, 0, 63))
    outside = []
    for e in (json.loads(log.page_body(tpl, s, s + 63))["entries"]
              for s in range(0, 1024, 64)):
        for entry in e:
            leaf = base64.b64decode(entry["leaf_input"])
            extra = base64.b64decode(entry["extra_data"])
            n, m = (int.from_bytes(leaf[12:15], "big"),
                    int.from_bytes(extra[3:6], "big"))
            outside.append((leaf[15:15 + n], extra[6:6 + m]))
    assert len(doc["entries"]) == 64 and len(outside) == 1024
    res = agg.ingest(outside[:1000])
    assert (np.asarray(res.was_unknown)[:1000] == ~log.is_dup[:1000]).all()

    ini = harness.write_ini(
        {"directives": {"backend": "tpu", "tableBits": 12}}, str(tmp_path),
        "report.ini", path, ["http://127.0.0.1:1/log0"], {})
    report = harness.report_child(ini)
    assert report["totals"]["serials"] == standing.rows
    got = {i["id"]: i["serials"] for i in report["issuers"]}
    assert got == dict(zip(tpl.issuer_ids, standing.by_issuer().tolist()))
    assert {e for i in report["issuers"] for e in i["expDates"]} \
        == {tpl.exp_date_id}


def test_the_programs_own_save_of_the_rows_is_the_fixtures_base(
        tmp_path, suite_devices):
    """(b) The same rows folded by the program from their certificates
    and saved by it: the same set of (key, meta) rows, the same fill of
    every bucket and the same small members as the fixture's base."""
    standing, tpl, hour = standing_12()
    path, _ = prefill.ensure(str(tmp_path), standing, tpl.issuer_ids, hour)
    agg = aggregator()
    certs = standing_certs(standing, tpl)
    for lo in range(0, standing.rows, 1024):
        assert np.asarray(agg.ingest(certs[lo:lo + 1024]).was_unknown)[
            :1024].all()
    own = str(tmp_path / "own.npz")
    agg.save_checkpoint(own)
    theirs, their_fill, z_own = base_rows(own)
    mine, my_fill, z = base_rows(path)
    assert len(mine) == standing.rows and mine == theirs
    assert my_fill.dtype == their_fill.dtype == np.uint8
    assert (my_fill == their_fill).all()
    assert sorted(z.files) == sorted(z_own.files)
    for name in ("count", "layout", "n_shards", "base_hour", "registry",
                 "issuer_totals", "verify_verified", "verify_failed",
                 "host_keys"):
        assert z[name].dtype == z_own[name].dtype, name
        assert z[name].shape == z_own[name].shape, name
        assert (z[name] == z_own[name]).all(), name
    for name in ("keys", "meta"):
        assert z[name].dtype == z_own[name].dtype
        assert z[name].shape == z_own[name].shape
    with open(path + prefill.MANIFEST_SUFFIX) as a, \
            open(own + prefill.MANIFEST_SUFFIX) as b:
        theirs_man, mine_man = json.load(b), json.load(a)
    assert sorted(mine_man) == sorted(theirs_man)
    with open(path, "rb") as fh:
        assert mine_man["baseSha256"] == hashlib.sha256(fh.read()).hexdigest()
    assert {k: v for k, v in mine_man.items() if k != "baseSha256"} \
        == {k: v for k, v in theirs_man.items() if k != "baseSha256"}


def test_a_row_past_a_full_bucket_lies_where_the_program_looks():
    """Placement where buckets overflow (load 0.85 of 6,144 slots): every
    bucket holds 24 at most, a row away from its home passed only full
    buckets, and the program's host reader finds every row."""
    from ct_mapreduce_tpu.ops import buckettable

    standing, tpl, hour = standing_12()
    standing = prefill.Standing(12, 0.85, 16, 1.1)
    table = prefill.members(standing, tpl.issuer_ids, hour)
    fill = table["fill"].astype(np.int64)
    assert fill.max() == 24 and int(fill.sum()) == standing.rows
    keys = table["keys"]
    home = ((keys[:, 0] ^ (keys[:, 1] * np.uint32(0x9E3779B9)))
            & np.uint32(255)).astype(np.int64)
    at = np.repeat(np.arange(256), fill)
    moved = np.flatnonzero(home != at)
    assert moved.size > 0
    for h, b in zip(home[moved], at[moved]):
        assert all(fill[(h + d) % 256] == 24 for d in range((b - h) % 256))
    rows = buckettable.unpack_np(table["fill"], keys, table["meta"])
    assert buckettable.contains_np(rows, keys).all()


@pytest.mark.parametrize("logs", [1, 3])
@pytest.mark.parametrize("known_share", [0.0, 0.5, 1.0])
def test_fixture_counts_with_a_standing_table_against_a_slow_recount(
        known_share, logs):
    """(c) Uniques and per-issuer counts, recounted over a brute-force
    set of every standing serial and every entry."""
    block = dict(BLOCK, known_share=known_share)
    spec = fx.LogSpec(**dict(SPEC, logs=logs, window_entries=512 * logs,
                             table_prefill=block))
    run = fx.RunFixture(spec, 2**31 + 77)
    standing = spec.standing
    seen = {prefill.SERIAL_BASE + j: int(k) for j, k in enumerate(
        standing.issuer_of(np.arange(standing.rows)))}
    assert len(seen) == 3072
    repeats = 0
    for log in run.logs:
        for i in range(log.total):
            j = int(log.standing_of[i])
            serial = int(log.serial_of[i]) if j < 0 else prefill.SERIAL_BASE + j
            if serial in seen:
                assert seen[serial] == int(log.issuer_of[i])
                assert log.is_dup[i] or j >= 0
                repeats += j >= 0 and not log.is_dup[i]
            else:
                assert not log.is_dup[i] and j < 0
                seen[serial] = int(log.issuer_of[i])
    assert run.expected_unique() == len(seen)
    recount = np.bincount(list(seen.values()), minlength=16)
    assert (run.expected_by_issuer() == recount).all()
    assert (run.standing_by_issuer() + run.new_by_issuer() == recount).all()
    originals = run.offered - run.duplicates
    if known_share == 0.0:
        assert repeats == 0 and len(seen) == 3072 + originals
    elif known_share == 1.0:
        assert repeats == originals and len(seen) == 3072
    else:
        assert 0.4 * originals < repeats < 0.6 * originals
    # No standing row is repeated twice but by a repeat within a log.
    rows = np.concatenate([log.standing_of[(log.standing_of >= 0)
                                           & ~log.is_dup] for log in run.logs])
    assert len(set(rows.tolist())) == len(rows) == repeats


def test_pages_carry_the_standing_serials():
    x509 = pytest.importorskip("cryptography.x509")
    tpl = fx.Templates()
    log = fx.LogFixture(fx.LogSpec(**dict(SPEC, table_prefill=BLOCK)), 9, 0)
    doc = json.loads(log.page_body(tpl, 64, 127))
    known = 0
    for i, e in enumerate(doc["entries"], 64):
        leaf = base64.b64decode(e["leaf_input"])
        n = int.from_bytes(leaf[12:15], "big")
        cert = x509.load_der_x509_certificate(leaf[15:15 + n])
        j = int(log.standing_of[i])
        want = int(log.serial_of[i]) if j < 0 else prefill.SERIAL_BASE + j
        assert cert.serial_number == (0x4D << 120) + want
        known += j >= 0
    assert 16 < known < 48


@pytest.mark.parametrize("seed", ["0", "7", str(2**31 + 12345)])
def test_a_traffic_file_without_the_block_gives_the_parents_pages(seed):
    """(d) The arrays and pages of a run without ``table_prefill``,
    against what the parent commit (PR 46) gave: hashed there, committed
    under ``data/``."""
    with open(os.path.join(HERE, "data", "fixture_arrays_pr46.json")) as fh:
        parent = json.load(fh)
    run = fx.RunFixture(fx.LogSpec(**parent["spec"]), int(seed))
    tpl = fx.Templates()
    want = parent["seeds"][seed]
    assert run.expected_unique() == want["unique"]
    assert run.expected_by_issuer().tolist() == want["by_issuer"]
    for log, hashes in zip(run.logs, want["logs"]):
        assert log.standing_of is None
        for name in ("is_dup", "serial_of", "issuer_of", "kind_of"):
            assert hashlib.sha256(getattr(log, name).tobytes()).hexdigest() \
                == hashes[name], name
        assert hashlib.sha256(b"".join(
            log.page_body(tpl, s, s + 63)
            for s in range(0, log.total, 64))).hexdigest() == hashes["pages"]


def test_the_cache_name_moves_with_every_parameter_and_the_source():
    """(f)"""
    standing, tpl, hour = standing_12()
    ids = tpl.issuer_ids
    name = prefill.cache_name(standing, ids, hour)
    assert name == prefill.cache_name(prefill.Standing(12, 0.5, 16, 1.1),
                                      list(ids), hour)
    assert prefill.source_hash() in name and name.endswith(".npz")
    others = {
        prefill.cache_name(prefill.Standing(13, 0.5, 16, 1.1), ids, hour),
        prefill.cache_name(prefill.Standing(12, 0.25, 16, 1.1), ids, hour),
        prefill.cache_name(prefill.Standing(12, 0.5, 8, 1.1), ids, hour),
        prefill.cache_name(prefill.Standing(12, 0.5, 16, 1.2), ids, hour),
        prefill.cache_name(standing, ids, hour + 1),
        prefill.cache_name(standing, ids[::-1], hour),
        prefill.cache_name(standing, ids, hour, source="0" * 12)}
    assert len(others) == 7 and name not in others
    # known_share is the stream's, not the table's.
    assert prefill.Standing.of(dict(BLOCK, known_share=1.0), 16, 1.1) \
        == standing


def test_load_is_a_share_of_the_slots_the_program_builds():
    """``load`` x the table's real slots: 24 a bucket, a power of two of
    buckets, as ``ops/buckettable.py::bucket_count`` rounds them."""
    from ct_mapreduce_tpu.ops import buckettable

    for bits in (8, 12, 16, 18, 26, 27):
        assert prefill.table_slots(bits) \
            == buckettable.bucket_count(1 << bits) * buckettable.SLOTS
    assert prefill.table_slots(27) == 201_326_592
    at_27 = prefill.Standing(27, 0.5, 16, 1.1)
    assert at_27.rows == 100_663_296 == int(at_27.by_issuer().sum())
    assert at_27.by_issuer()[0] == at_27.by_issuer().max()
    assert at_27.issuer_of(np.array([0, at_27.rows - 1])).tolist() == [0, 15]


# -- the harness ----------------------------------------------------------


@pytest.fixture
def checkout(tmp_path):
    """A directory shaped like the checkout (links to ``benchmark/``, the
    package and ``BENCHMARK.json``) for a rehearsal to keep its state in;
    ``benchmark/`` computes every path from where its files lie."""
    root = tmp_path / "loaded_cell_checkout"  # tier-1's own is "checkout"
    root.mkdir()
    for name in ("benchmark", "ct_mapreduce_tpu", "BENCHMARK.json"):
        os.symlink(os.path.join(ROOT, name), root / name)
    return str(root)


def rehearsal(root: str, script: str, *args: str):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "tests", script),
         *args], capture_output=True, text=True, timeout=600, env=env,
        cwd=root)


def rehearse_cell(root: str, *args: str) -> list:
    res = rehearsal(root, "rehearse_cell.py", CELL, *args)
    assert res.stdout.strip(), res.stderr[-2000:]
    return [json.loads(x) for x in res.stdout.strip().splitlines()]


def checks_of(lines: list) -> dict:
    return {x["what"]: x for x in lines if isinstance(x, dict) and "what" in x}


RESTORE = "restore: rows the live table held when the warm-up round was folded"
MISSING = "durable report: standing rows missing"


def test_the_committed_cell_is_correct_with_ten_comparisons(checkout):
    """The cell's own files cut to a rehearsal's size, traced: the ten
    comparisons, the standing rows in the report, the second run of the
    checkout finds the table built, and every metric of the host's
    layers that lists the cell has something to read."""
    lines = rehearse_cell(checkout, "777001", "trace")
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0, lines[-12:]
    checks = checks_of(lines)
    assert len(checks) == 10 and list(checks)[-2:] == [RESTORE, MISSING]
    slots = prefill.table_slots(18)
    assert checks[RESTORE]["want"] > slots // 2 == 196_608
    assert checks["durable report: unique serials"]["want"] > slots // 2
    setup = next(x["setup"] for x in lines if isinstance(x, dict)
                 and "setup" in x)
    assert setup["of_which_standing_table_built"] > 0.0
    metrics = next(x for x in lines if isinstance(x, list))[0]
    for name in ("loaded.restore_s", "loaded.table_load_pct",
                 "loaded.save_rows_m", "loaded.ckpt_save_s",
                 "loaded.ckpt_d2h_s", "loaded.ckpt_write_s",
                 "fetch.us_per_entry", "decode.ns_per_entry",
                 "fold.us_per_entry", "ckpt.drain_s", "loadgen.headroom_x"):
        assert metrics[name]["value"] > 0, name
    assert metrics["loaded.full_saves"]["value"] == 1.0
    assert metrics["ckpt.unpacked_saves"]["value"] == 0.0
    assert metrics["compile.programs"]["value"] >= 0.0
    # The load the program states at the end, in per cent, is the rows
    # the report counts over the table's slots.
    assert metrics["loaded.table_load_pct"]["value"] == pytest.approx(
        100.0 * checks["durable report: unique serials"]["got"] / slots)
    assert metrics["loaded.save_rows_m"]["value"] == pytest.approx(
        checks["durable report: unique serials"]["got"] / 1e6)
    # Neither a query plane's metric nor another cell's.
    assert not [n for n in metrics if n.startswith(
        ("serve.", "multilog.", "shard4.", "qshard4.", "gil."))]
    again = rehearse_cell(checkout, "777002")
    assert again[-1]["correct"] is True
    setup = next(x["setup"] for x in again if isinstance(x, dict)
                 and "setup" in x)
    assert setup["of_which_standing_table_built"] == 0.0
    assert len(os.listdir(os.path.join(
        checkout, ".bench_cache", "prefill"))) == 2  # the base, its manifest


def test_lost_standing_row_is_not_correct(checkout):
    """The control: one row the stream does not repeat is left out of
    the base. The report is one short, and the comparison named for it
    reads 1."""
    lines = rehearse_cell(checkout, "777003", "lost_standing_row")
    checks = checks_of(lines)
    assert lines[-1]["correct"] is False and lines[-1]["failed"] == 1
    assert checks[MISSING]["got"] == 1 and checks[MISSING]["ok"] is False
    assert checks[RESTORE]["got"] == checks[RESTORE]["want"] - 1
    total = checks["durable report: unique serials"]
    assert total["got"] == total["want"] - 1
    assert all(c["ok"] for what, c in checks.items()
               if what.startswith(("live:", "round:")))


def test_restore_ignored_is_not_correct(checkout):
    """The control: the base is gone when the program starts. The live
    table holds the warm-up round's own rows when that round is folded
    and no more."""
    lines = rehearse_cell(checkout, "777004", "restore_ignored")
    checks = checks_of(lines)
    assert lines[-1]["correct"] is False
    assert checks[RESTORE]["ok"] is False
    assert 0 < checks[RESTORE]["got"] <= 1024  # one batch of a rehearsal
    assert checks[RESTORE]["want"] > prefill.table_slots(18) // 2
    assert checks[MISSING]["got"] > 0


def test_lost_entry_is_not_correct_in_the_loaded_cell(checkout):
    """The control the other cells have, here: the entry lost is one
    that repeats no standing row (a known certificate lost changes no
    count), and the report is short by it."""
    lines = rehearse_cell(checkout, "777005", "lost_entry")
    checks = checks_of(lines)
    total = checks["durable report: unique serials"]
    assert lines[-1]["correct"] is False
    assert 1 <= total["want"] - total["got"] == lines[-1]["failed"] <= 4
    assert checks[RESTORE]["ok"] is True  # the table was restored whole


def test_slots_log2_that_is_not_tablebits_fails_before_jax_loads(
        checkout, tmp_path):
    """(e)"""
    with open(os.path.join(BENCH, "traffic", CELL + ".json")) as fh:
        traffic = json.load(fh)
    g = traffic["generators"][0]
    g.update(page=64, warmup_entries=1024, window_entries_per_second=1024,
             table_prefill=dict(g["table_prefill"], slots_log2=17))
    path = str(tmp_path / "wrong.json")
    with open(path, "w") as fh:
        json.dump(traffic, fh)
    res = rehearsal(checkout, "rehearse.py", "1", "5", "notrace",
                    "traffic=" + path)  # rehearse.py's table: tableBits 16
    assert res.returncode == 4 and res.stdout.strip() == ""
    assert "slots_log2 17 is not the configuration's tableBits 16" \
        in res.stderr
    assert "(jax loaded: False)" in res.stderr
    assert not os.path.exists(os.path.join(checkout, ".bench_cache"))
    g["table_prefill"] = {"slots_log2": 16, "load": 0.5}
    with pytest.raises(harness.RunFailed, match="known_share"):
        harness.log_spec(traffic, 6.0, 1024)


def test_the_two_readers_of_the_harness_clock():
    """``loaded.restore_s`` and ``loaded.table_load_pct`` on a hand-made
    run: the first page request after ``ct_fetch.main`` was called (the
    traced run's headroom probe fetched pages before it), and the gauge
    as it stood when the round was durable."""
    from readers import harness_number

    out = {"t_main_called": 100.0, "load_at_durable": 0.5076,
           "all_pages": [[0, 0, 512, 98.0, 98.1, 98.2],
                         [0, 0, 512, 141.5, 141.6, 141.7],
                         [0, 512, 512, 141.8, 141.9, 142.0]]}
    ctx = {"out": out, "values": {}}
    assert harness_number.read({"which": "restore_s"}, ctx) \
        == pytest.approx(41.5)
    assert harness_number.read({"which": "table_load_pct"}, ctx) \
        == pytest.approx(50.76)
    ctx["out"] = dict(out, load_at_durable=None, all_pages=out["all_pages"][:1])
    assert harness_number.read({"which": "restore_s"}, ctx) is None
    assert harness_number.read({"which": "table_load_pct"}, ctx) is None
    stamper = harness.FoldStamper()
    stamper.set_gauge("aggregator.table_load", 0.5)
    stamper.set_gauge("shard.fill_max", 9.0)
    assert [v for _, v in stamper.loads] == [0.5]
    assert stamper.load_at(stamper.loads[0][0]) == 0.5
    assert stamper.load_at(stamper.loads[0][0] - 1.0) is None


# -- what BENCHMARK.json lists ---------------------------------------------


def whole_bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def test_the_cell_and_its_configuration_are_what_the_issue_names():
    bench = whole_bench()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["config"] == CONFIG and cell["chips"] == 1
    assert cell["traffic"] == CELL and cell == bench["workloads"][-1]
    assert len(bench["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 2
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["issuers", "tableBits"]
    with open(os.path.join(ROOT, entry["file"])) as fh:
        config = json.load(fh)
    with open(os.path.join(BENCH, "configs", "icarus-dedup-1chip.json")) as fh:
        plain = json.load(fh)
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    assert config["directives"] == dict(plain["directives"], tableBits=27)
    assert config["guarantees"] == dict(
        plain["guarantees"], resume=config["guarantees"]["resume"])
    assert "table_prefill" not in config["reduced"]
    assert set(config["assumed"]) == {"load", "known_share",
                                      *plain["assumed"]}
    with open(os.path.join(BENCH, "traffic", CELL + ".json")) as fh:
        traffic = json.load(fh)
    assert traffic["generators"] == [{
        "kind": "log_replay", "logs": 1, "page": 512, "dup_share": 0.03,
        "leaf_mix": {"rsa2048": 0.7, "ec_p256": 0.3}, "issuers": 16,
        "zipf_s": 1.1, "warmup_entries": 65536,
        "window_entries_per_second": 75000, "ramp_batches": 4,
        "tail_batches": 3,
        "table_prefill": {"slots_log2": 27, "load": 0.5,
                          "known_share": 0.5}}]
    spec = harness.log_spec(traffic, float(bench["run_seconds"]), 65536)
    assert spec.window_entries == 40 * 65536 and spec.per_log == 47 * 65536
    assert spec.standing.rows == 100_663_296
    # The end-to-end metrics and their bounds are as they were.
    assert [(m["name"], m["bound"]) for m in bench["end_to_end"]] == [
        ("ingest_entries_per_s", 0.16), ("setup_s", 0.25)]


def test_every_metric_that_lists_the_cell_has_its_reader():
    bench = whole_bench()
    assert all("workloads" in m for m in bench["per_layer"])
    mine = [m for m in bench["per_layer"] if CELL in m["workloads"]]
    own = [m for m in mine if m["workloads"] == [CELL]]
    assert tuple(m["name"] for m in own) == OWN
    assert own == bench["per_layer"][-len(OWN):]  # the cell's block ends the list
    assert tuple(m["name"] for m in mine if m not in own) == JOINED
    layers_named = {m["layer"] for m in bench["per_layer"] if m not in own}
    for m in own:
        assert m["layer"] in layers_named  # no layer of its own spelling
        with open(os.path.join(BENCH, "layers", m["name"] + ".json")) as fh:
            spec = json.load(fh)
        assert os.path.exists(os.path.join(
            BENCH, "readers", spec["reader"] + ".py"))
    # The five the span ring's tier-1 test holds to backfill-1log alone
    # are read here by the same files under the cell's name.
    for name, older in (("loaded.ckpt_save_s", "ckpt.save_s"),
                        ("loaded.ckpt_d2h_s", "ckpt.d2h_s"),
                        ("loaded.ckpt_write_s", "ckpt.write_s"),
                        ("loaded.sink_starved_share", "sink.starved_share"),
                        ("loaded.fetch_blocked_share", "fetch.blocked_share"),
                        ("loaded.full_saves", "multilog.full_saves")):
        with open(os.path.join(BENCH, "layers", name + ".json"), "rb") as a, \
                open(os.path.join(BENCH, "layers", older + ".json"), "rb") as b:
            assert a.read() == b.read()
        if older != "multilog.full_saves":
            was = next(m for m in bench["per_layer"] if m["name"] == older)
            assert was["workloads"] == ["backfill-1log"]
            now = next(m for m in own if m["name"] == name)
            assert {k: v for k, v in now.items()
                    if k not in ("name", "workloads")} \
                == {k: v for k, v in was.items()
                    if k not in ("name", "workloads")}
    ctx = {"out": {"counters": [], "t_open": 0.0, "t_durable": 1.0}}
    assert layers.read_metrics(
        [m for m in own if m["name"] == "loaded.restore_s"],
        "backfill-1log", ctx) == ({}, [])  # no other cell reads them


def test_the_older_cells_tests_read_the_list_without_the_cell():
    """``listing.before``: the per-cell tests written before this cell
    (and their tier-1 wrappers, which hold places in ``per_layer``) see
    the 106 metrics, five cells and five configurations of PR 46, every
    ``workloads`` as it was."""
    bench = whole_bench()
    then = listing.before(bench)
    assert [w["name"] for w in then["workloads"]] \
        == [w["name"] for w in bench["workloads"][:-1]]
    assert [c["name"] for c in then["configs"]] \
        == [c["name"] for c in bench["configs"][:-1]]
    assert len(then["per_layer"]) == 106 == len(bench["per_layer"]) - len(OWN)
    assert not [m for m in then["per_layer"] if CELL in m["workloads"]]
    for was, now in zip(then["per_layer"], bench["per_layer"]):
        assert now == dict(was, workloads=now["workloads"])
        assert now["workloads"] in (was["workloads"],
                                    was["workloads"] + [CELL])
    assert tuple(now["name"] for was, now in zip(
        then["per_layer"], bench["per_layer"])
        if now["workloads"] != was["workloads"]) == JOINED
    assert listing.before(bench, later=()) == bench
