"""Tests of the cell ``backfill-1log-cnfilter`` and of what it added to
the benchmark: the configuration and traffic files as ISSUE 52 names
them, every listed metric's file and reader, the arithmetic of the
window under the filter, a rehearsal of the committed cell and its three
controls. By hand, on the CPU:

  python3 -m pytest benchmark/tests/test_cnfilter_cell.py -q

Tier-1 runs them through ``tests/test_benchmark_cnfilter_cell.py``. They
find the cell's entries by NAME, never by place, so that a cell listed
after this one breaks none of them.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import cells  # noqa: E402
import fixture as fx  # noqa: E402
import harness  # noqa: E402

CELL = "backfill-1log-cnfilter"
CONFIG = "icarus-cnfilter-1chip"
CONTROL = "backfill-1log"  # the same stream with the mechanism off
PREFIX = "Bench Issuer CA 00"
# The three the ``filter.`` family brought, and the thirteen older
# readers the cell lists again under its own name: a PR that may only
# add cannot join an older metric's ``workloads`` (README, "The rule for
# the next cell"), so each is the older file, letter for letter.
OWN = ("cnfilter.dropped_pct", "cnfilter.undecidable_lanes",
       "cnfilter.host_dropped_lanes")
COPIES = {
    "cnfilter.step_device_ns_per_entry": "step.device_ns_per_entry",
    "cnfilter.sha256_roofline": "sha256_roofline",
    "cnfilter.fold_us_per_entry": "fold.us_per_entry",
    "cnfilter.decode_ns_per_entry": "decode.ns_per_entry",
    "cnfilter.fetch_us_per_entry": "fetch.us_per_entry",
    "cnfilter.h2d_ms_per_batch": "h2d.ms_per_batch",
    "cnfilter.sink_starved_share": "sink.starved_share",
    "cnfilter.fetch_blocked_share": "fetch.blocked_share",
    "cnfilter.loadgen_headroom_x": "loadgen.headroom_x",
    "cnfilter.ckpt_drain_s": "ckpt.drain_s",
    "cnfilter.compile_programs": "compile.programs",
    "cnfilter.device_idle_pct": "device.idle_pct",
    "cnfilter.peak_hbm_gb": "device.peak_hbm_gb",
}
DROPPED = "live: entries the filter dropped"
UNIQUE = "durable report: unique serials"
BY_ISSUER = "durable report: per-issuer counts that differ"


def bench_json() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def json_file(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


# -- what BENCHMARK.json lists ---------------------------------------------


def test_the_cell_and_its_configuration_are_what_the_issue_names():
    bench = bench_json()
    cell = cells.named(bench["workloads"], CELL)
    assert cell["config"] == CONFIG and cell["traffic"] == CELL
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bench["workloads"]) \
        <= len(bench["workloads"]) // 2
    entry = cells.named(bench["configs"], CONFIG)
    assert entry["reduced"] == ["table_prefill", "issuers", "tableBits"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    config = json_file("configs", CONFIG + ".json")
    plain = json_file("configs", "icarus-dedup-1chip.json")
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    assert "configs[1]" in config["source"]
    # The plain dedup configuration's ten directives to the letter, and
    # the one this deployment is about.
    assert config["directives"] == dict(plain["directives"],
                                        issuerCNFilter=PREFIX)
    assert list(config["directives"])[-1] == "issuerCNFilter"
    assert harness.cn_prefixes(config) == (PREFIX,)
    said = json_file("tests", "data", "cn-filter-config.json")["guarantees"]
    assert config["guarantees"] == dict(
        plain["guarantees"], filter=said["filter"],
        filter_count=config["guarantees"]["filter_count"])
    assert "exact" in config["guarantees"]["filter_count"]
    assert config["reduced"] == plain["reduced"]
    assert set(config["not_cuts"]) == {"limit", "noopbackend"}
    assert set(config["assumed"]) == {"filtered_issuer", "prefix",
                                      *plain["assumed"]}
    assert "limit" not in config["directives"]
    traffic = json_file("traffic", CELL + ".json")
    theirs = json_file("traffic", CONTROL + ".json")
    assert traffic["generators"] == [
        dict(theirs["generators"][0], window_entries_per_second=75000)]
    assert traffic["loop"] == theirs["loop"]
    assert traffic["window"] == theirs["window"]
    assert [(m["name"], m["bound"]) for m in bench["end_to_end"]] == [
        ("ingest_entries_per_s", 0.16), ("setup_s", 0.25)]


def test_every_listed_metric_has_its_file_and_the_copies_are_copies():
    bench = bench_json()
    mine = cells.metrics_of(bench, CELL)
    assert {m["name"] for m in mine} == set(OWN) | set(COPIES)
    assert all(m["workloads"] == [CELL] for m in mine)
    assert len(bench["per_layer"]) <= 128  # the file's own limit
    # Appended: nothing stands behind the cell's block.
    assert [m["name"] for m in bench["per_layer"][-len(mine):]] \
        == [m["name"] for m in mine]
    for m in mine:
        spec = cells.has_its_reader(BENCH, m, bench)
        older = COPIES.get(m["name"])
        if older is None:
            assert m["source"] == "program_counter"
            assert spec["reader"] in ("counter_ratio", "counter_sum")
            assert all(key.startswith("filter.cn_") for key in [
                spec["params"]["key"], *spec["params"].get("over", [])])
            continue
        assert spec == json_file("layers", older + ".json"), m["name"]
        theirs = cells.named(bench["per_layer"], older)
        assert CELL not in theirs["workloads"]
        assert {k: m[k] for k in m if k not in ("name", "workloads")} \
            == {k: theirs[k] for k in theirs if k not in ("name", "workloads")}
    assert cells.named(mine, "cnfilter.compile_programs")["moves"] == "setup_s"
    ratio = json_file("layers", "cnfilter.dropped_pct.json")["params"]
    assert ratio == {"key": "filter.cn_dropped", "scale": 100.0,
                     "over": ["filter.cn_dropped", "filter.cn_passed"]}


def test_the_window_under_the_filter_is_the_issues_arithmetic():
    bench = bench_json()
    traffic = json_file("traffic", CELL + ".json")
    config = json_file("configs", CONFIG + ".json")
    spec = harness.log_spec(traffic, float(bench["run_seconds"]), 65536)
    assert spec.window_entries == 40 * 65536 == 2_621_440
    assert spec.ramp_entries == 4 * 65536 and spec.tail_entries == 3 * 65536
    assert spec.warmup_entries + spec.per_log == 48 * 65536
    assert spec.standing is None  # a filter and a standing table: refused
    # The filtered CA is the log's head issuer: Zipf 1.1 over sixteen
    # gives it one entry in 3.0293.
    share = fx.zipf_weights(16, 1.1)[0]
    assert share == pytest.approx(1 / 3.0293, rel=1e-4)
    assert round(spec.window_entries * share, -3) == 865_000
    prefixes = harness.cn_prefixes(config)
    assert fx.issuers_kept(prefixes, 16).tolist() == [True] + [False] * 15
    # A large seed, as the driver's are.
    run = fx.RunFixture(spec, 2**31 + 52, prefixes)
    log = run.logs[0]
    lo = spec.warmup_entries + spec.ramp_entries
    passing = int((log.issuer_of[lo:lo + spec.window_entries] == 0).sum())
    assert abs(passing - 865_362) < 5_000
    assert run.filtered_out == int((log.issuer_of != 0).sum())
    assert run.offered == 48 * 65536
    assert run.expected_by_issuer()[1:].sum() == 0
    assert 0 < run.expected_unique() < run.offered - run.filtered_out


# -- the committed cell, cut to a rehearsal's size ---------------------------


@pytest.fixture
def checkout(tmp_path):
    """A directory shaped like the checkout (links to ``benchmark/``, the
    package and ``BENCHMARK.json``) for a rehearsal to keep its state in;
    ``benchmark/`` computes every path from where its files lie."""
    root = tmp_path / "cnfilter_cell_checkout"  # tier-1's own is "checkout"
    root.mkdir()
    for name in ("benchmark", "ct_mapreduce_tpu", "BENCHMARK.json"):
        os.symlink(os.path.join(ROOT, name), root / name)
    return str(root)


def rehearse_cell(root: str, *args: str) -> tuple[list, str]:
    """``breaks_cnfilter.py rehearse``: ``rehearse_cell.py``'s run (the
    cell's files at ``tableBits`` 18, batches of 1,024) with this cell's
    controls known beside the older ones."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    res = subprocess.run(
        [sys.executable,
         os.path.join(root, "benchmark", "tests", "breaks_cnfilter.py"),
         "rehearse", CELL, *args], capture_output=True, text=True,
        timeout=600, env=env, cwd=root)
    assert res.stdout.strip(), res.stderr[-2000:]
    return [json.loads(x) for x in res.stdout.strip().splitlines()], res.stderr


def checks_of(lines: list) -> dict:
    return {x["what"]: x for x in lines if isinstance(x, dict) and "what" in x}


def test_the_committed_cell_is_correct_with_nine_comparisons(checkout):
    """Traced: the nine comparisons, the ninth the program's own count;
    what the predicate decided read from the ``filter.`` family; every
    metric that lists the cell and needs no device reads a number."""
    seed = 2**31 + 5201
    lines, stderr = rehearse_cell(checkout, str(seed), "trace")
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0, lines[-12:]
    assert f"IssuerCNFilter enabled: ['{PREFIX}']" in stderr
    checks = checks_of(lines)
    assert len(checks) == 9 and list(checks)[-1] == DROPPED
    assert checks[DROPPED]["got"] == checks[DROPPED]["want"] > 0
    assert checks["round: programs compiled"]["got"] == 0
    assert 0 < checks[UNIQUE]["want"] \
        < checks["live: entries submitted to the device"]["want"] \
        - checks[DROPPED]["want"]
    metrics = next(x for x in lines if isinstance(x, list))[0]
    # cells.CHIP_ONLY knows the allocator's peak by its older name only.
    host = cells.read_on_the_host(cells.bench_json(checkout), CELL) \
        - {"cnfilter.peak_hbm_gb"}
    assert set(metrics) == host == (set(OWN) | set(COPIES)) - {
        "cnfilter.step_device_ns_per_entry", "cnfilter.sha256_roofline",
        "cnfilter.device_idle_pct", "cnfilter.peak_hbm_gb"}
    assert all(isinstance(m["value"], float) for m in metrics.values())
    # The window's folds alone: the fixture says what they dropped.
    config, traffic = json_file("configs", CONFIG + ".json"), \
        json_file("traffic", CELL + ".json")
    import rehearse_cell as cut

    config, traffic = cut.tiny(config, traffic)
    spec = harness.log_spec(traffic, float(bench_json()["run_seconds"]),
                            cut.TINY_BATCH)
    log = fx.RunFixture(spec, seed, (PREFIX,)).logs[0]
    lo = spec.warmup_entries + spec.ramp_entries
    dropped = int((log.issuer_of[lo:lo + spec.window_entries] != 0).sum())
    assert metrics["cnfilter.dropped_pct"]["value"] == pytest.approx(
        100.0 * dropped / spec.window_entries)
    assert 60.0 < metrics["cnfilter.dropped_pct"]["value"] < 72.0
    assert metrics["cnfilter.undecidable_lanes"]["value"] == 0.0
    assert metrics["cnfilter.host_dropped_lanes"]["value"] == 0.0
    assert metrics["cnfilter.compile_programs"]["value"] > 0.0
    assert metrics["cnfilter.fold_us_per_entry"]["value"] > 0.0


@pytest.mark.parametrize("broken, not_ok", [
    ("filter_ignored", [UNIQUE, BY_ISSUER, DROPPED]),
    ("drop_uncounted", [DROPPED]),
    ("lost_entry", [UNIQUE, BY_ISSUER, DROPPED]),
])
def test_a_control_is_not_correct(checkout, broken, not_ok):
    """``filter_ignored``: the report holds sixteen issuers and nothing
    is counted; ``drop_uncounted``: the report is right and the process
    states no count (the program before ISSUE 52); ``lost_entry``: an
    entry of the passing issuer is missing from the report, and the
    entry that took its place was another issuer's at least once."""
    lines, _stderr = rehearse_cell(checkout, str(2**31 + 5202), broken)
    checks = checks_of(lines)
    assert lines[-1]["correct"] is False
    assert lines[-1]["not_ok"] == not_ok
    assert len(checks) == 9
    if broken == "filter_ignored":
        assert checks[BY_ISSUER]["got"] == 15
        assert checks[DROPPED]["got"] is None
    elif broken == "drop_uncounted":
        assert checks[DROPPED]["got"] is None and lines[-1]["failed"] == 0
    else:
        assert checks[UNIQUE]["got"] < checks[UNIQUE]["want"]
        assert checks[DROPPED]["got"] > checks[DROPPED]["want"]
