"""Tests of the benchmark's own arithmetic and of its comparison, run by
hand on the CPU:  python3 -m pytest benchmark/tests -q

The rehearsals drive a whole run through ``harness.run_cell`` at a tiny
table, without the look for a chip that ``run.py`` makes first.
"""

from __future__ import annotations

import base64
import gzip
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

import fixture as fx  # noqa: E402
import harness  # noqa: E402
import kernel_cost  # noqa: E402
import logserver  # noqa: E402
import tracing  # noqa: E402

SPEC = dict(logs=2, page=64, dup_share=0.05,
            leaf_mix={"rsa2048": 0.7, "ec_p256": 0.3}, issuers=16,
            zipf_s=1.1, warmup_entries=1024, window_entries=4096)


def test_window_is_whole_batches():
    assert fx.window_entries(9000, 35) == 5 * 65536
    assert fx.window_entries(9000, 20) == 3 * 65536
    assert fx.window_entries(100, 1) == 2 * 65536  # never under two
    assert fx.window_entries(9000, 35, logs=3) == 6 * 65536
    for seconds in (1, 10, 20, 35, 51):
        assert fx.window_entries(7000, seconds) % 65536 == 0


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_fixture_counts_against_a_slow_recount(seed):
    """N - D and the per-issuer counts, recounted entry by entry."""
    run = fx.RunFixture(fx.LogSpec(**SPEC), seed)
    seen: dict[int, int] = {}
    for log in run.logs:
        assert log.total == (1024 if log.index == 0 else 0) + 2048
        for i in range(log.total):
            serial = int(log.serial_of[i])
            if log.is_dup[i]:
                assert serial in seen  # repeats an earlier entry's
                assert seen[serial] == int(log.issuer_of[i])
            else:
                assert serial not in seen
                seen[serial] = int(log.issuer_of[i])
    assert run.expected_unique() == len(seen) == run.offered - run.duplicates
    recount = np.bincount(list(seen.values()), minlength=16)
    assert (run.expected_by_issuer() == recount).all()
    assert run.duplicates > 0
    # Zipf: the first issuer signs most.
    assert recount[0] == recount.max()
    again = fx.RunFixture(fx.LogSpec(**SPEC), seed)
    assert (again.logs[1].serial_of == run.logs[1].serial_of).all()


def test_pages_are_rfc6962_and_carry_the_fixture():
    x509 = pytest.importorskip("cryptography.x509")
    import hashlib

    from cryptography.hazmat.primitives import serialization

    tpl = fx.Templates()
    log = fx.RunFixture(fx.LogSpec(**SPEC), 5).logs[0]
    doc = json.loads(log.page_body(tpl, 128, 999))
    assert len(doc["entries"]) == 64  # cut to a page
    for j, e in enumerate(doc["entries"]):
        leaf = base64.b64decode(e["leaf_input"])
        assert leaf[:2] == b"\x00\x00" and leaf[10:12] == b"\x00\x00"
        n = int.from_bytes(leaf[12:15], "big")
        der, ext = leaf[15:15 + n], leaf[15 + n:]
        assert ext == b"\x00\x00"
        cert = x509.load_der_x509_certificate(der)
        want = int(log.serial_of[128 + j])
        assert cert.serial_number == int("4d" + "%030x" % want, 16)
        extra = base64.b64decode(e["extra_data"])
        m = int.from_bytes(extra[3:6], "big")
        ca = x509.load_der_x509_certificate(extra[6:6 + m])
        assert cert.issuer == ca.subject
        spki = ca.public_key().public_bytes(
            serialization.Encoding.DER,
            serialization.PublicFormat.SubjectPublicKeyInfo)
        assert base64.urlsafe_b64encode(hashlib.sha256(spki).digest()) \
            .decode() == tpl.issuer_ids[int(log.issuer_of[128 + j])]


def test_log_server_serves_the_fixture_pages_from_memory():
    spec = fx.LogSpec(**dict(SPEC, ramp_entries=2048, tail_entries=2048))
    state = logserver.LogState(spec, 11)
    tpl = fx.Templates()
    for log in state.run.logs:
        assert log.total == (1024 if log.index == 0 else 0) + 4096
        starts = [s for k, s in state.bodies if k == log.index]
        assert sorted(starts) == list(range(0, log.total, 64))
        for start in (0, 64, log.total - 64):
            assert state.bodies[log.index, start] == \
                log.page_body(tpl, start, start + 999)
    assert state.tree_size(state.run.logs[0]) == 1024
    assert state.tree_size(state.run.logs[1]) == 0


def test_kernel_cost():
    cost = kernel_cost.sha256_single_block(65536)
    assert cost["hbm_bytes"] == 65536 * 96
    assert cost["int32_ops"] == 65536 * (48 * 21 + 64 * 36 + 8)


def test_trace_reduction_synthetic():
    xp = {"devices": {"/device:TPU:0": {
        "ops": [("a", 0.0, 1.0), ("b", 0.5, 1.0), ("a", 5.0, 1.0),
                ("k", 6.00001, 0.5)],
        "modules": [("jit_x", 0.0, 1.5)]}}, "host": []}
    spans = {"x": [(1.0, 3.0)], "y": [(2.0, 4.0), (4.5, 5.0)]}
    r = tracing.reduce_trace(xp, 0.0, 6.50001, spans)
    assert r["busy_s"] == pytest.approx(3.0)
    gaps = dict(r["idle_gaps"])
    assert gaps["x"] == pytest.approx(1.125)
    assert gaps["y"] == pytest.approx(1.875)
    assert gaps["no_span"] == pytest.approx(0.5)
    assert r["device_ops"][0] == ["a", 2.0]
    # Clipped to a window: half of the first op, and the gap up to 3.0.
    r = tracing.reduce_trace(xp, 0.25, 3.0, spans)
    assert r["busy_s"] == pytest.approx(1.25)
    assert r["window_s"] == pytest.approx(2.75)
    assert dict(r["idle_gaps"])["x"] == pytest.approx(1.5 * 1.5 / 2.5)
    assert tracing.short("%while.74 = (s32[]{:T(128)}, u32[4,1]) while(") \
        == "while.74"
    # A span that wraps others leaves them their parts of the gap and
    # keeps its own self time.
    r = tracing.reduce_trace(xp, 0.0, 6.50001, {
        "ingest.decode": [(1.5, 5.0)], "native.decode_batch": [(2.0, 4.0)],
        "decode.native_call": [(2.5, 3.0)], "decode.pack": [(3.0, 3.75)]})
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"ingest.decode": 1.5, "native.decode_batch": 0.75,
         "decode.native_call": 0.5, "decode.pack": 0.75, "no_span": 0.0,
         "within_program": 1e-5}, abs=1e-9)
    assert tracing.less([(0, 10), (12, 14)], [(1, 2), (3, 4), (9, 13)]) \
        == [(0, 1), (2, 3), (4, 9), (13, 14)]


def test_trace_reduction_on_the_recorded_trace():
    """A traced window of ``ct-fetch`` ingest on a TPU v5e, flattened by
    ``tracing.load_xplane`` and kept as JSON: the numbers below were read
    off it once by hand-checked arithmetic and must not move."""
    path = os.path.join(HERE, "data", "recorded_trace.json.gz")
    if not os.path.exists(path):
        pytest.skip("no recorded trace in this checkout")
    with gzip.open(path, "rt") as fh:
        doc = json.load(fh)
    spans = {k: [tuple(iv) for iv in v] for k, v in doc["host_spans"].items()}
    lo, hi = doc["window"]
    r = tracing.reduce_trace(doc["xplane"], lo, hi, spans)
    plane = next(iter(doc["xplane"]["devices"].values()))
    assert 0.0 < r["busy_s"] < hi - lo
    assert r["busy_s"] <= sum(d for _n, _s, d in plane["ops"]) + 1e-9
    idle = sum(v for _k, v in r["idle_gaps"])
    assert idle == pytest.approx((hi - lo) - r["busy_s"], abs=1e-3)
    assert r["busy_s"] == pytest.approx(doc["expect"]["busy_s"], rel=1e-9)
    assert r["device_ops"][0][0] == doc["expect"]["top_op"]
    # The readers on it: one 65,536-lane ingest step lies in these five
    # seconds, 25.2 ms of device time, its SHA kernel 0.278 ms.
    from readers import device_time, kernel_roofline

    ctx = {"trace": r, "config": {"directives": {"batchSize": 65536}},
           "device": {"kind": "TPU v5 lite"}}
    assert device_time.read(
        {"what": "modules", "match": "ingest", "lanes_per_call": "batchSize",
         "scale": 1e9}, ctx) == pytest.approx(385.05, abs=0.01)
    roof = kernel_roofline.read(
        {"match": "_single_block_pallas", "cost": "sha256_single_block",
         "lanes_per_call": "batchSize"}, ctx)
    assert roof == pytest.approx(2.762, abs=0.001) and roof < 100.0
    assert device_time.read({"what": "idle_pct"}, ctx) \
        == pytest.approx(99.465, abs=0.001)
    with pytest.raises(KeyError):
        kernel_roofline.read(
            {"match": "_single_block_pallas", "cost": "sha256_single_block",
             "lanes_per_call": 65536},
            dict(ctx, device={"kind": "some other chip"}))


def rehearsal(*args: str) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"), *args],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)


def rehearse(*args: str) -> dict:
    res = rehearsal(*args)
    assert res.stdout.strip(), res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("logs", [1, 3])
def test_sound_run_is_correct(logs):
    """The whole run at a tiny table; ``logs`` = 3 is the traffic file's
    parameter of the cell PERF.md keeps for later."""
    line = rehearse(str(logs), "31337")
    assert line["values"]["ingest_entries_per_s"] > 0 and line["failed"] == 0
    # Key for key what a run with the log alone always printed, and the
    # parts by generator.
    assert list(line) == ["correct", "attempted", "failed", "values",
                          "not_ok", "device", "by_generator"]
    assert list(line["values"]) == ["ingest_entries_per_s", "setup_s"]
    assert line["by_generator"] == {"log_replay": {
        "attempted": line["attempted"], "failed": 0}}
    if logs == 1:
        assert line["correct"] is True
    else:
        # Every count is exact. What the program does with several logs
        # today (each log's exit save flushes a partial batch) the run
        # reports as a batch beyond the fixture's, and may as a compile.
        assert set(line["not_ok"]) <= {
            "round: batches folded beyond the fixture's",
            "round: programs compiled"}


def test_traced_rehearsal_reads_the_host_layers():
    """The traced path end to end; without a chip only the metrics of
    the host's layers have something to read."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"), "1", "31339",
         "trace"], capture_output=True, text=True, timeout=600, env=env,
        cwd=ROOT)
    lines = [json.loads(x) for x in res.stdout.strip().splitlines()]
    assert lines[-1]["correct"] is True, res.stderr[-2000:]
    metrics = next(x for x in lines if isinstance(x, list))[0]
    for name in ("fetch.us_per_entry", "decode.ns_per_entry",
                 "fold.us_per_entry", "ckpt.drain_s", "ckpt.mb_per_s",
                 "loadgen.headroom_x", "compile.programs"):
        assert metrics[name]["value"] > 0, name
    assert "device.idle_pct" not in metrics


def test_lost_entry_is_not_correct():
    """The control, at a size a test can hold: the timed path broken
    underneath, and ``correct`` comes out false."""
    line = rehearse("1", "31338", "notrace", "lost_entry")
    assert line["correct"] is False and line["failed"] == 3


def test_deferred_checkpoint_is_not_correct():
    """The program says idle before the round's checkpoint is on disk.
    What is compared is the file as it stood at that instant: the
    warm-up round's."""
    line = rehearse("1", "31338", "notrace", "deferred_checkpoint")
    assert line["correct"] is False
    assert line["not_ok"][0] == "durable report: unique serials"
    kept = harness.report_child(
        os.path.join(ROOT, ".bench_work", "report.ini"))
    assert 0 < kept["totals"]["serials"] <= 1024  # the warm-up batch's


def query_step(*args: str) -> dict:
    """``sweep_query.py``'s cell (the log and ``query_poisson``, a
    configuration that asks for ``queryPort``) cut to a rehearsal's
    size, through ``harness.Prepared`` and ``run_cell``."""
    res = subprocess.run(
        [sys.executable, os.path.join(HERE, "sweep_query.py"), "step", "40",
         *args, "tiny"], capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert res.stdout.strip(), res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def query_traffic(tmp_path, name: str, generators) -> str:
    """The sweep's traffic file cut to size, its generators beside the
    log replaced by ``generators(them)``, as a file."""
    sys.path.insert(0, HERE)
    import sweep_query

    _config, traffic = sweep_query.cell(40.0, tiny=True)
    traffic["generators"][1:] = generators(traffic["generators"][1:])
    path = os.path.join(tmp_path, name)
    with open(path, "w") as fh:
        json.dump(traffic, fh)
    return path


def test_query_generator_beside_the_log_is_correct():
    """A traffic file with ``query_poisson`` beside the log and a
    configuration that asks for a port: files and nothing else. The port
    taken reaches the ini and the generator's spec."""
    line = query_step("31340")
    assert line["correct"] is True, line["not_ok"]
    parts = line["by_generator"]
    assert list(parts) == ["log_replay", "query_poisson"]
    assert parts["query_poisson"]["attempted"] > 100
    assert parts["query_poisson"]["failed"] == 0
    values = line["values"]
    assert values["query_sent"] == parts["query_poisson"]["attempted"]
    assert 0 < values["query_p50_ms"] <= values["query_p95_ms"] \
        <= values["query_p99_ms"] < 10_000
    work = os.path.join(ROOT, ".bench_work")
    with open(os.path.join(work, "query_poisson.spec.json")) as fh:
        spec = json.load(fh)
    with open(os.path.join(work, "ct-fetch.ini")) as fh:
        ini = dict(x.strip().split(" =", 1) for x in fh if " =" in x)
    assert set(spec["ports"]) == {"metricsPort", "queryPort"}
    assert int(ini["queryPort"]) == spec["ports"]["queryPort"]
    assert int(ini["metricsPort"]) == spec["ports"]["metricsPort"]
    assert spec["generator"]["kind"] == "query_poisson"
    # The harness names neither the kind nor the directive.
    for name in ("harness.py", "run.py", "layers.py"):
        with open(os.path.join(BENCH, name)) as fh:
            text = fh.read()
        assert "query_poisson" not in text and "queryPort" not in text


def test_wrong_answer_is_not_correct():
    """The control of a cell with queries: every seventh membership
    answer inverted underneath, and ``correct`` comes out false by the
    generator's own check."""
    line = query_step("31341", "wrong_answer")
    assert line["correct"] is False
    assert [w[:40] for w in line["not_ok"]] == [
        "query_poisson: answers that contradict t"]
    assert line["by_generator"]["query_poisson"]["failed"] > 0
    assert line["by_generator"]["log_replay"]["failed"] == 0


def test_a_cell_whose_files_are_wrong_fails_before_jax_loads(tmp_path):
    res = rehearsal("1", "5", "notrace", "traffic=" + query_traffic(
        tmp_path, "unknown.json", lambda gs: [dict(gs[0], kind="no_such_kind")]))
    assert res.returncode == 4 and res.stdout.strip() == ""
    assert "benchmark/generators/no_such_kind.py is not there" in res.stderr
    assert "(jax loaded: False)" in res.stderr
    # Two generators that give the same values: a name given twice.
    twice = query_traffic(tmp_path, "twice.json",
                          lambda gs: [gs[0], dict(gs[0], rate_per_s=8)])
    res = rehearsal("1", "5", "notrace", "traffic=" + twice)
    assert res.returncode == 4 and res.stdout.strip() == ""
    assert "values given twice: query_failed, query_p50_ms" in res.stderr
    assert "(jax loaded: False)" in res.stderr
    # No log, or two, is no traffic file either.
    with open(twice) as fh:
        traffic = json.load(fh)
    with pytest.raises(harness.RunFailed, match="exactly one log_replay"):
        harness.log_spec(dict(traffic, generators=traffic["generators"][1:]),
                         6.0, 1024)


def test_query_summary_counts_what_a_user_would():
    """``query_poisson.summarise`` on rows written by hand: latency from
    the instant a request was due, ten deadlines for one that failed,
    and the two checks."""
    from generators import query_poisson as qp

    spec = fx.LogSpec(**dict(SPEC, logs=1))
    fixture = fx.RunFixture(spec, 3)
    params = {"rate_per_s": 50.0, "known_share": 0.5, "zipf_s": 0.99,
              "min_age_s": 2.0, "deadline_s": 1.0}
    schedule = qp.Schedule(3, params)
    due = [100.0 + a[0] for a in schedule.chunk()]
    due = [t for t in due if 101.0 < t <= 103.0]
    assert 60 < len(due) < 140
    total = fixture.logs[0].total
    # The log served entries [0, 2048) at 90 s and the rest at 102 s.
    pages = [[0, 0, 2048, 89.9, 89.95, 90.0],
             [0, 2048, total - 2048, 101.9, 101.95, 102.0]]
    window = {"t_open": 100.0, "t_first": 101.0, "t_folded": 103.0,
              "pages": pages, "generator": params}
    rows = [[t, t + 0.001, t + 0.020, 200, 1, 0, 7, True] for t in due]
    rows += [[100.5, 100.5, 100.6, 200, 1, 0, 7, False]]  # before the window
    summarise = lambda rows: qp.summarise(  # noqa: E731
        {"requests": rows, "generator": {}}, window, fixture, {})
    got = summarise(rows)
    assert got["attempted"] == len(due) and got["failed"] == 0
    assert [c["got"] for c in got["checks"]] == [0, 0]
    assert got["values"]["query_p99_ms"] == pytest.approx(20.0)
    assert sorted(got["values"]) == sorted(qp.VALUES)
    # Refused, late, broken and wrong: each one failed, ten deadlines.
    rows[0][3] = 429
    rows[1][2] = rows[1][0] + 1.5
    rows[2][3], rows[2][7] = 0, None
    rows[3][7] = False          # a fed-and-aged serial answered unknown
    rows[4][4:8] = 0, 0, total + 5, True  # a never-fed one answered known
    got = summarise(rows)
    assert got["failed"] == 5 and got["values"]["query_failed"] == 5.0
    assert got["values"]["query_p99_ms"] == pytest.approx(10_000.0)
    assert got["values"]["query_p50_ms"] == pytest.approx(20.0)
    assert [c["got"] for c in got["checks"]] == [2, 0]
    # The generator's own faults: a serial not yet aged (entry 3000 was
    # served at 102 s), a fed one sent as never fed, one row missing; and
    # one sent late, which is counted apart.
    rows = [[t, t + 0.001, t + 0.020, 200, 1, 0, 7, True] for t in due]
    rows[0][1] += 0.2
    rows[1][6] = 3000
    rows[2][4:8] = 0, 0, 9, True
    got = summarise(rows[:-1])
    assert [c["got"] for c in got["checks"]] == [1, 3]
    assert got["values"]["query_sent_late"] == 1.0
    with pytest.raises(ValueError):
        summarise(rows[:0])
    aged = qp.Aged(1)
    aged.add(pages)
    assert [aged.entries(0, t) for t in (89.0, 90.0, 101.0, 102.5)] \
        == [0, 2048, 2048, total]


def test_run_py_refuses_without_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "backfill-1log", "--seed", "1", "--seconds", "2", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert res.returncode == 3
    assert res.stdout.strip() == ""
    assert "needs 1 TPU device" in res.stderr


def test_benchmark_json_keeps_the_contract():
    """The limits the driver checks before any run, as far as a test can
    restate them."""
    import re

    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        raw = fh.read()
    assert len(raw.encode()) <= 64 * 1024
    b = json.loads(raw)
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    line = lambda s: 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s  # noqa: E731
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    assert all(line(w) for w in b["command"]) and len(b["command"]) <= 32
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith("benchmark/") and os.path.exists(
            os.path.join(ROOT, c["file"]))
        assert len(c["reduced"]) <= 16 and all(name.match(k) for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as fh:
            assert set(c["reduced"]) == set(json.load(fh)["reduced"])
    cells = {w["name"] for w in b["workloads"]}
    assert len(cells) == len(b["workloads"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4) and line(w["why"])
        assert os.path.exists(os.path.join(BENCH, "traffic",
                                           w["traffic"] + ".json"))
    assert {w["config"] for w in b["workloads"]} == set(configs)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher") and 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        mine = [m for m in b["end_to_end"] if cell in m.get("workloads", cells)]
        assert len(mine) >= 2 and "setup_s" in {m["name"] for m in mine}
    names = set(e2e)
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["name"] not in names
        names.add(m["name"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert line(m["layer"]) and m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in e2e[m["moves"]].get("workloads", cells)
        assert os.path.exists(os.path.join(BENCH, "layers",
                                           m["name"] + ".json"))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    bad = re.compile(r"[^A-Za-z0-9_.\-/]")
    for base, _dirs, files in os.walk(BENCH):
        if "__pycache__" in base:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), ROOT)
            assert not bad.search(rel), rel


# Tier-1 reaches ``benchmark/tests`` through ``tests/test_benchmark_harness.py``,
# which runs every test this module holds when it imports it (as
# ``benchmark.tests.test_benchmark``), "and whatever test is added there
# later". The tests of the cell ``backfill-1log-loaded`` are a file of
# their own, ``test_loaded_cell.py``; this hands them to tier-1, with
# the fixtures they use, and leaves them to their own file by hand.
if __name__ != "test_benchmark":
    sys.path.insert(0, HERE)
    import test_loaded_cell as _loaded  # noqa: E402

    globals().update({name: thing for name, thing in vars(_loaded).items()
                      if name.startswith("test_")
                      or name in ("checkout", "suite_devices")})
