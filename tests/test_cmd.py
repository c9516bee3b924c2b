"""CLI binaries: ct-fetch, storage-statistics, ct-getcert.

End-to-end over the in-process fake log and tmp state, matching the
reference binaries' flows (cmd/ct-fetch/ct-fetch.go:490-638,
cmd/storage-statistics/storage-statistics.go:22-100,
cmd/ct-getcert/ct-getcert.go:16-57).
"""

import datetime
import io
import sys
from unittest import mock

import pytest

from ct_mapreduce_tpu.cmd import ct_fetch, ct_getcert, storage_statistics
from ct_mapreduce_tpu.config import CTConfig

from tests import certgen
from tests.fakelog import FakeLog

UTC = datetime.timezone.utc
FUTURE = datetime.datetime(2031, 6, 15, tzinfo=UTC)


def _fake_log(n=6, issuer_cn="CLI CA", dupes=0):
    log = FakeLog()
    issuer_der = certgen.make_cert(serial=1, issuer_cn=issuer_cn, is_ca=True,
                                   not_after=FUTURE)
    for s in range(n):
        leaf = certgen.make_cert(
            serial=1000 + (s % (n - dupes) if dupes else s),
            issuer_cn=issuer_cn, subject_cn="cli.example.com",
            is_ca=False, not_after=FUTURE,
        )
        log.add_cert(leaf, issuer_der, timestamp_ms=1700000000000 + s)
    return log


def _patch_transport(monkeypatch, log):
    """Route CTLogClient's default transport to the fake log."""
    from ct_mapreduce_tpu.ingest import ctclient

    monkeypatch.setattr(ctclient, "_default_transport", log.transport)


@pytest.mark.parametrize("mesh_shape,expect_sharded", [
    ("shard:1", False),  # explicit single chip -> TpuAggregator
    ("", True),          # default: all 8 virtual devices, sharded
    ("shard:8", True),   # explicit mesh (BASELINE config #5's shape)
])
def test_ct_fetch_tpu_backend_and_statistics(tmp_path, monkeypatch, capsys,
                                             mesh_shape, expect_sharded):
    """TPU-backend CLI flow across aggregator selections: ct-fetch
    ingests through the device pipeline (single-chip or all_to_all
    mesh-sharded per meshShape), snapshots, and storage-statistics
    drains the snapshot identically in every case."""
    from ct_mapreduce_tpu.agg import sharded_agg

    sharded_built = []
    orig_sharded = sharded_agg.ShardedAggregator

    class SpyShardedAggregator(orig_sharded):
        def __init__(self, *a, **k):
            sharded_built.append(True)
            super().__init__(*a, **k)

    monkeypatch.setattr(sharded_agg, "ShardedAggregator",
                        SpyShardedAggregator)
    log = _fake_log(n=6, dupes=2)
    _patch_transport(monkeypatch, log)
    ini = tmp_path / "ct.ini"
    state = tmp_path / "agg.npz"
    ini.write_text(
        f"logList = {log.url}\n"
        "backend = tpu\n"
        "batchSize = 64\n"
        "tableBits = 12\n"
        + (f"meshShape = {mesh_shape}\n" if mesh_shape else "")
        + f"aggStatePath = {state}\n"
        "healthAddr = \n"
        "nobars = true\n"
    )
    rc = ct_fetch.main(["-config", str(ini), "-nobars"])
    assert rc == 0
    assert state.exists()
    # meshShape really drives aggregator selection (empty = all
    # visible devices -> sharded on the 8-device virtual mesh).
    assert bool(sharded_built) == expect_sharded

    rc = storage_statistics.main(["-config", str(ini), "-v", "1"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "overall totals: 1 issuers, 4 serials" in out
    assert "Issuer: " in out and "CLI CA" in out


def test_ct_fetch_database_backend(tmp_path, monkeypatch, capsys):
    log = _fake_log(n=5)
    _patch_transport(monkeypatch, log)
    certs = tmp_path / "certs"
    ini = tmp_path / "ct.ini"
    ini.write_text(
        f"logList = {log.url}\n"
        f"certPath = {certs}\n"
        "healthAddr = \n"
    )
    rc = ct_fetch.main(["-config", str(ini), "-nobars"])
    assert rc == 0
    # PEMs landed in the <exp>/<issuer>/<serial> tree
    pems = list(certs.rglob("*"))
    assert any(p.is_file() for p in pems)
    # checkpoint file written under state/
    assert (certs / "state").exists()


def test_ct_fetch_tpu_backend_with_certpath_writes_pems(tmp_path, monkeypatch):
    """backend=tpu + certPath keeps the reference's durable PEM tree
    (filesystemdatabase.go:189-208): one PEM per first-seen cert in
    <exp>/<issuer>/<serial>, plus dirty markers."""
    log = _fake_log(n=5, dupes=1)
    _patch_transport(monkeypatch, log)
    certs = tmp_path / "certs"
    ini = tmp_path / "ct.ini"
    ini.write_text(
        f"logList = {log.url}\n"
        "backend = tpu\n"
        "batchSize = 64\n"
        "tableBits = 12\n"
        f"certPath = {certs}\n"
        f"aggStatePath = {tmp_path / 'agg.npz'}\n"
        "healthAddr = \n"
    )
    rc = ct_fetch.main(["-config", str(ini), "-nobars"])
    assert rc == 0
    pems = [p for p in certs.rglob("*") if p.is_file()
            and "state" not in p.parts and not p.name.startswith(".")]
    assert len(pems) == 4  # 5 entries, 1 dupe
    assert pems[0].read_bytes().startswith(b"-----BEGIN CERTIFICATE-----")
    assert list(certs.rglob(".dirty")) or list(certs.rglob("*dirty*"))


def test_storage_statistics_tpu_v2_v3(tmp_path, monkeypatch, capsys):
    """--backend=tpu verbosity parity (storage-statistics.go:28-99):
    -v2 lists serials (PEM-tree + host-lane), -v3 dumps the PEMs. With
    certPath set during the fetch, every first-seen cert is listable."""
    log = _fake_log(n=5, dupes=1)
    _patch_transport(monkeypatch, log)
    certs = tmp_path / "certs"
    ini = tmp_path / "ct.ini"
    ini.write_text(
        f"logList = {log.url}\n"
        "backend = tpu\n"
        "batchSize = 64\n"
        "tableBits = 12\n"
        f"certPath = {certs}\n"
        f"aggStatePath = {tmp_path / 'agg.npz'}\n"
        "healthAddr = \n"
    )
    assert ct_fetch.main(["-config", str(ini), "-nobars"]) == 0

    rc = storage_statistics.main(["-config", str(ini), "-v", "2"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "Serials: [" in out
    # 4 distinct serials (5 entries, 1 dupe), all listable via the tree.
    import re

    listed = re.findall(r"Serials: \[([^\]]*)\]", out)
    n_listed = sum(len([x for x in blob.split(",") if x.strip()])
                   for blob in listed)
    assert n_listed == 4
    assert "count-only" not in out  # nothing unlisted when certPath set

    rc = storage_statistics.main(["-config", str(ini), "-v", "3"])
    assert rc == 0
    out = capsys.readouterr().out
    assert out.count("-----BEGIN CERTIFICATE-----") == 4
    assert "Certificate serial={" in out

    # Without the PEM tree, device-lane serials are count-only and say so.
    ini2 = tmp_path / "ct2.ini"
    ini2.write_text(
        f"logList = {log.url}\n"
        "backend = tpu\n"
        "batchSize = 64\n"
        "tableBits = 12\n"
        f"aggStatePath = {tmp_path / 'agg.npz'}\n"
        "healthAddr = \n"
    )
    rc = storage_statistics.main(["-config", str(ini2), "-v", "2"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "count-only" in out


def test_storage_statistics_tpu_log_status_and_host_only(
        tmp_path, monkeypatch, capsys):
    """TPU mode prints the per-log "Log status:" section exactly like
    database mode (storage-statistics.go:86-98) — the cursor is
    dual-written through the same facade regardless of backend — and
    the report is pure host work: the snapshot reader's table state
    stays NumPy end to end (report must run during TPU pool outages)."""
    import numpy as np

    log = _fake_log(n=5)
    _patch_transport(monkeypatch, log)
    certs = tmp_path / "certs"
    state = tmp_path / "agg.npz"
    ini = tmp_path / "ct.ini"
    ini.write_text(
        f"logList = {log.url}\n"
        "backend = tpu\n"
        "batchSize = 64\n"
        "tableBits = 12\n"
        f"certPath = {certs}\n"
        f"aggStatePath = {state}\n"
        "healthAddr = \n"
    )
    assert ct_fetch.main(["-config", str(ini), "-nobars"]) == 0

    rc = storage_statistics.main(["-config", str(ini)])
    assert rc == 0
    tpu_out = capsys.readouterr().out
    assert "Log status:" in tpu_out
    assert "MaxEntry=5" in tpu_out
    tpu_status = tpu_out.split("Log status:")[1]

    # Database mode over the same certPath prints the identical status
    # lines (same facade walk, backend-fallback read of the cursor).
    buf = io.StringIO()
    rc = storage_statistics.report_from_database(
        CTConfig.load(["-config", str(ini)]), buf)
    assert rc == 0
    db_status = buf.getvalue().split("Log status:")[1]
    # LastUpdateTime differs per read only if rewritten; here both read
    # the same stored state — the lines must match byte for byte.
    assert tpu_status == db_status

    # Host-only residency: no jax arrays anywhere in the read path, and
    # the drain matches the device aggregator's drain on the same file.
    from ct_mapreduce_tpu.agg.aggregator import (
        HostSnapshotAggregator, TpuAggregator)

    host = HostSnapshotAggregator(capacity=1 << 10)
    host.load_checkpoint(str(state))
    assert isinstance(host.table.keys, np.ndarray)
    host_snap = host.drain()
    assert isinstance(host.table.keys, np.ndarray)
    dev = TpuAggregator(capacity=1 << 10)
    dev.load_checkpoint(str(state))
    dev_snap = dev.drain()
    assert host_snap.counts == dev_snap.counts
    assert host_snap.crls == dev_snap.crls
    assert host_snap.dns == dev_snap.dns


def test_ct_fetch_requires_loglist(capsys):
    rc = ct_fetch.main(["-nobars"])
    assert rc == 2


def test_ct_fetch_offset_limit(tmp_path, monkeypatch):
    log = _fake_log(n=10)
    _patch_transport(monkeypatch, log)
    ini = tmp_path / "ct.ini"
    state = tmp_path / "agg.npz"
    ini.write_text(
        f"logList = {log.url}\n"
        "backend = tpu\n"
        "tableBits = 12\n"
        f"aggStatePath = {state}\n"
        "healthAddr = \n"
    )
    rc = ct_fetch.main(
        ["-config", str(ini), "-nobars", "-offset", "2", "-limit", "3"]
    )
    assert rc == 0
    # entries 2,3,4 → 3 distinct serials
    out = io.StringIO()
    cfg = CTConfig.load(["-config", str(ini)])
    storage_statistics.report_from_tpu_snapshot(cfg, out)
    assert "3 serials" in out.getvalue()


def test_storage_statistics_parity_mode(tmp_path, monkeypatch, capsys):
    # Parity mode walks the same database the fetch wrote (in-process
    # MockRemoteCache means both must share one engine invocation).
    from ct_mapreduce_tpu.engine import get_configured_storage
    from ct_mapreduce_tpu.ingest.sync import DatabaseSink, LogSyncEngine

    log = _fake_log(n=4)
    cfg = CTConfig.load([])
    database, cache, backend = get_configured_storage(cfg)
    sink = DatabaseSink(database, now=datetime.datetime(2025, 1, 1, tzinfo=UTC))
    engine = LogSyncEngine(sink, database, num_threads=1)
    engine.start_store_threads()
    engine.sync_log(log.url, transport=log.transport)
    engine.wait_for_downloads(timeout=30)
    engine.stop()

    out = io.StringIO()
    with mock.patch(
        "ct_mapreduce_tpu.cmd.storage_statistics.get_configured_storage",
        return_value=(database, cache, backend),
    ):
        rc = storage_statistics.report_from_database(cfg, out, verbosity=2)
    assert rc == 0
    text = out.getvalue()
    assert "overall totals: 1 issuers, 4 serials" in text
    assert "Serials:" in text


def test_storage_statistics_json_parity(tmp_path, monkeypatch, capsys):
    """--json emits the same numbers the text report prints (ISSUE 5
    satellite): totals line vs totals object, per-expDate -v1 counts
    vs the expDates maps, and the Log status walk."""
    import json
    import re

    log = _fake_log(n=6, dupes=2)
    _patch_transport(monkeypatch, log)
    ini = tmp_path / "ct.ini"
    state = tmp_path / "agg.npz"
    ini.write_text(
        f"logList = {log.url}\n"
        "backend = tpu\n"
        "batchSize = 64\n"
        "tableBits = 12\n"
        f"aggStatePath = {state}\n"
        "healthAddr = \n"
    )
    assert ct_fetch.main(["-config", str(ini), "-nobars"]) == 0

    rc = storage_statistics.main(["-config", str(ini), "-v", "1"])
    assert rc == 0
    text = capsys.readouterr().out

    rc = storage_statistics.main(["-config", str(ini), "-json"])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)

    m = re.search(r"overall totals: (\d+) issuers, (\d+) serials, "
                  r"(\d+) crls", text)
    assert m
    assert report["totals"] == {
        "issuers": int(m.group(1)),
        "serials": int(m.group(2)),
        "crls": int(m.group(3)),
    }
    # Per-expDate counts match the -v1 bullet lines number for number.
    text_counts = dict(re.findall(r"- (\S+) \((\d+) serials\)", text))
    json_counts = {
        exp: str(n)
        for iss in report["issuers"]
        for exp, n in iss["expDates"].items()
    }
    assert json_counts == text_counts
    for iss in report["issuers"]:
        assert iss["serials"] == sum(iss["expDates"].values())
        assert f"Issuer: {iss['id']}" in text
    # Log status rides along as data.
    status_lines = text.split("Log status:")[1].strip().splitlines()
    assert report["logStatus"] == [ln for ln in status_lines if ln]

    # Database mode --json: same collector shape over the cache walk.
    from ct_mapreduce_tpu.engine import get_configured_storage
    from ct_mapreduce_tpu.ingest.sync import DatabaseSink, LogSyncEngine

    cfg = CTConfig.load([])
    database, cache, backend = get_configured_storage(cfg)
    sink = DatabaseSink(database,
                       now=datetime.datetime(2025, 1, 1, tzinfo=UTC))
    engine = LogSyncEngine(sink, database, num_threads=1)
    engine.start_store_threads()
    engine.sync_log(log.url, transport=log.transport)
    engine.wait_for_downloads(timeout=30)
    engine.stop()
    with mock.patch(
        "ct_mapreduce_tpu.cmd.storage_statistics.get_configured_storage",
        return_value=(database, cache, backend),
    ):
        db_report = storage_statistics.collect_database_report(cfg)
    assert db_report["totals"] == report["totals"]


def test_ct_getcert(capsys):
    log = _fake_log(n=3)
    out = io.StringIO()
    rc = ct_getcert.main(
        ["-log", log.url, "-index", "1"], transport=log.transport, out=out
    )
    assert rc == 0
    pem = out.getvalue()
    assert pem.startswith("-----BEGIN CERTIFICATE-----")
    # round-trip: the PEM decodes back to the cert at index 1
    import base64

    body = "".join(pem.splitlines()[1:-1])
    der = base64.b64decode(body)
    from ct_mapreduce_tpu.core import der as hostder

    fields = hostder.parse_cert(der)
    assert fields.serial == (1001).to_bytes(2, "big")


def test_ct_getcert_routes_via_query_plane(tmp_path):
    """queryPort satellite: with a query plane up, ct-getcert fetches
    through its /getcert proxy (no direct log transport at all); with
    the plane down, it falls back to the direct transport."""
    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
    from ct_mapreduce_tpu.serve.server import QueryServer

    log = _fake_log(n=3)
    agg = TpuAggregator(capacity=1 << 10, batch_size=64)
    srv = QueryServer(agg, 0, host="127.0.0.1",
                      transport=log.transport).start()
    try:
        out = io.StringIO()
        # transport=None: a direct log fetch would hit the network and
        # fail — success proves the plane served the PEM.
        rc = ct_getcert.main(
            ["-log", log.url, "-index", "1",
             "-queryAddr", f"127.0.0.1:{srv.port}"],
            transport=None, out=out,
        )
        assert rc == 0
        assert out.getvalue().startswith("-----BEGIN CERTIFICATE-----")

        # The config path resolves queryPort the same way.
        ini = tmp_path / "q.ini"
        ini.write_text(f"queryPort = {srv.port}\n")
        out = io.StringIO()
        rc = ct_getcert.main(
            ["-log", log.url, "-index", "0", "-config", str(ini)],
            transport=None, out=out,
        )
        assert rc == 0
        assert out.getvalue().startswith("-----BEGIN CERTIFICATE-----")
    finally:
        srv.stop()

    # Plane gone: the same invocation falls back to the given direct
    # transport and still succeeds.
    out = io.StringIO()
    rc = ct_getcert.main(
        ["-log", log.url, "-index", "1",
         "-queryAddr", f"127.0.0.1:{srv.port}"],
        transport=log.transport, out=out,
    )
    assert rc == 0
    assert out.getvalue().startswith("-----BEGIN CERTIFICATE-----")


def test_ct_query_cli(capsys):
    """ct-query end to end: known serial exits 0, unknown exits 1,
    issuer metadata and health print JSON, unreachable plane exits 2."""
    import json

    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
    from ct_mapreduce_tpu.cmd import ct_query
    from ct_mapreduce_tpu.core import der as hostder
    from ct_mapreduce_tpu.core.types import ExpDate, Issuer
    from ct_mapreduce_tpu.serve.server import QueryServer
    from ct_mapreduce_tpu.utils import syncerts

    tpl = syncerts.make_template(issuer_cn="Query CLI CA")
    agg = TpuAggregator(capacity=1 << 12, batch_size=64)
    agg.ingest([(syncerts.stamp_serial(tpl, j), tpl.issuer_der)
                for j in range(5)])
    issuer_id = Issuer.from_spki(
        hostder.parse_cert(tpl.issuer_der).spki).id()
    eh = hostder.parse_cert(tpl.leaf_der).not_after_unix_hour
    exp_id = ExpDate.from_unix_hour(eh).id()

    def serial_hex(j):
        der = syncerts.stamp_serial(tpl, j)
        return der[tpl.serial_off:tpl.serial_off + tpl.serial_len].hex()

    srv = QueryServer(agg, 0, host="127.0.0.1", max_delay_s=0.001).start()
    try:
        addr = f"127.0.0.1:{srv.port}"
        out = io.StringIO()
        rc = ct_query.main(
            ["-addr", addr, "-issuer", issuer_id, "-expDate", exp_id,
             "-serial", serial_hex(0), "-serial", serial_hex(4)],
            out=out,
        )
        assert rc == 0
        resp = json.loads(out.getvalue())
        assert [r["known"] for r in resp["results"]] == [True, True]
        assert resp["epoch"] >= 1 and "staleness_s" in resp

        out = io.StringIO()
        rc = ct_query.main(
            ["-addr", addr, "-issuer", issuer_id, "-expDate", exp_id,
             "-serial", serial_hex(999)],
            out=out,
        )
        assert rc == 1  # unknown serial, grep-style exit

        out = io.StringIO()
        rc = ct_query.main(["-addr", addr, "-issuerMeta", issuer_id],
                           out=out)
        assert rc == 0
        assert json.loads(out.getvalue())["unknown_total"] == 5

        out = io.StringIO()
        rc = ct_query.main(["-addr", addr, "-health"], out=out)
        assert rc == 0
        assert json.loads(out.getvalue())["healthy"] is True
    finally:
        srv.stop()
    # Plane gone: transport error exits 2.
    rc = ct_query.main(
        ["-addr", f"127.0.0.1:{srv.port}", "-health"], out=io.StringIO())
    assert rc == 2


@pytest.mark.parametrize("flag", ["-issuer", "-issuerMeta"])
def test_ct_query_takes_an_issuer_id_that_begins_with_a_dash(flag):
    """One issuerID in 64 begins with ``-`` (base64url). Such an ID after
    its flag is a value, not an option: the call gets as far as the
    plane, which is not there (2), and is not refused as usage
    (``SystemExit``)."""
    from ct_mapreduce_tpu.cmd import ct_query

    argv = ["-addr", "127.0.0.1:1", flag,
            "-5T3W1fDwyr37nUKmB4sOcdnSGqMysFozk-41H4WBtc="]
    if flag == "-issuer":
        argv += ["-expDate", "2031-06-15-00", "-serial", "4d00"]
    assert ct_query.main(argv, out=io.StringIO()) == 2


def test_ct_fetch_starts_query_plane(tmp_path, monkeypatch):
    """queryPort on ct-fetch: the query plane answers membership for
    the serials the run just ingested — asserted from inside the run
    via the engine's store path (the server outlives sync_log but not
    main), so we probe after main() via a spy that captured the port.

    The plane binds an ephemeral port (queryPort directive value 0 is
    'off', so the test patches QueryServer to record the bound port
    and uses a fixed free one)."""
    import socket

    from ct_mapreduce_tpu.serve import server as serve_server

    log = _fake_log(n=5, dupes=1)
    _patch_transport(monkeypatch, log)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    probed = {}
    orig_stop = serve_server.QueryServer.stop

    def spy_stop(self):
        # Probe while the plane is still serving (just before ct-fetch
        # tears it down): the live aggregator answers.
        try:
            from ct_mapreduce_tpu.serve.client import QueryClient

            probed["health"] = QueryClient(
                f"127.0.0.1:{self.port}").healthz()
        finally:
            orig_stop(self)

    monkeypatch.setattr(serve_server.QueryServer, "stop", spy_stop)
    ini = tmp_path / "ct.ini"
    ini.write_text(
        f"logList = {log.url}\n"
        "backend = tpu\n"
        "batchSize = 64\n"
        "tableBits = 12\n"
        f"aggStatePath = {tmp_path / 'agg.npz'}\n"
        f"queryPort = {port}\n"
        "healthAddr = \n"
    )
    rc = ct_fetch.main(["-config", str(ini), "-nobars"])
    assert rc == 0
    assert probed["health"]["healthy"] is True


def test_healthz_body_of_a_tpu_run_has_its_ingest_keys(tmp_path, monkeypatch):
    """``metricsPort`` on a plain ``backend = tpu`` run: the ``/healthz``
    body says the engine's stage, its last progress, each log's
    position and the entry channel's depth with its unit, and nothing
    of a dispatch mode that does not exist (PR 46 removed the overlap
    scheduler's ``overlap_queues`` and the staged ring's
    ``staging_ring*``). Read as the endpoint serves it, just before
    ct-fetch stops the server."""
    import socket

    from ct_mapreduce_tpu.telemetry import promhttp

    log = _fake_log(n=5, dupes=1)
    _patch_transport(monkeypatch, log)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    probed = {}
    orig_stop = promhttp.MetricsServer.stop

    def spy_stop(self):
        try:
            probed["code"], probed["body"] = self.healthz()
        finally:
            orig_stop(self)

    monkeypatch.setattr(promhttp.MetricsServer, "stop", spy_stop)
    ini = tmp_path / "ct.ini"
    ini.write_text(
        f"logList = {log.url}\n"
        "backend = tpu\n"
        "batchSize = 64\n"
        "tableBits = 12\n"
        "meshShape = shard:1\n"
        f"aggStatePath = {tmp_path / 'agg.npz'}\n"
        f"metricsPort = {port}\n"
        "healthAddr = \n"
    )
    assert ct_fetch.main(["-config", str(ini), "-nobars"]) == 0
    body = probed["body"]
    assert probed["code"] == 200 and body["healthy"] is True
    assert set(body) == {"time", "healthy", "stage", "last_progress",
                         "progress", "entry_queue_depth",
                         "entry_queue_depth_unit"}
    assert body["stage"] == "stopped"
    assert body["last_progress"] is not None
    (progress,) = body["progress"].values()
    assert progress == {"pos": 5, "end": 4}  # next index, last index
    assert body["entry_queue_depth"] == 0
    assert body["entry_queue_depth_unit"] == "pages"
    assert not [k for k in body if "overlap" in k or "staging" in k]
