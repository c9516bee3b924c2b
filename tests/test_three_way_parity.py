"""One stream, three implementations, the same answers.

The device pipeline (``AggregatorSink.store_raw_batch`` on a
``TpuAggregator``), the byte-exact host lane (``decode_entry`` then
``TpuAggregator._host_exact``) and the reference-shaped storage path
(``DatabaseSink`` → ``FilesystemDatabase`` → ``RedisCache`` over a real
TCP socket, read back as ``storage-statistics`` reads it) are each
tested against their own expectations elsewhere. This is the one place
they are held to each other, entry for entry, on one wire stream: the
plain reference the benchmark's exact comparison rests on.
"""

import base64

import pytest

from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
from ct_mapreduce_tpu.ingest.leaf import decode_entry
from ct_mapreduce_tpu.ingest.sync import (
    AggregatorSink,
    DatabaseSink,
    RawBatch,
)
from ct_mapreduce_tpu.storage.certdb import FilesystemDatabase
from ct_mapreduce_tpu.storage.noop import NoopBackend
from ct_mapreduce_tpu.storage.rediscache import RedisCache
from ct_mapreduce_tpu.utils import syncerts
from ct_mapreduce_tpu.utils.miniredis import MiniRedis

N = 512
CHUNK = 256
UNIQUE = 448
# The last 64 entries repeat the first 64 serials: true duplicates
# (same issuer, same expiry), two chunks apart.
SERIALS = list(range(UNIQUE)) + list(range(N - UNIQUE))


def _templates(kind: str):
    if kind == "rsa2048":
        # Rich-extension RSA-2048 leaves: over the narrow row width, so
        # the 2048-wide decode and step are the ones that run.
        return [syncerts.make_template(
            issuer_cn=f"Parity RSA CA {k}", key_type="rsa2048",
            serial_len=20, rich_extensions=True) for k in range(2)]
    return [syncerts.make_template(issuer_cn=f"Parity EC CA {k}")
            for k in range(2)]


def _per_issuer(counts: dict) -> list[int]:
    out: dict = {}
    for (issuer, _exp), n in counts.items():
        out[issuer] = out.get(issuer, 0) + n
    return sorted(out.values())


@pytest.mark.timeout(120)
@pytest.mark.parametrize("kind", ["ec-minimal", "rsa2048"])
def test_device_host_and_rediscache_agree_on_one_stream(kind):
    tpls = _templates(kind)
    lis, eds = syncerts.make_wire_batch(tpls, 0, N, serials=SERIALS)

    # (1) the device pipeline, a chunk at a time through the sink.
    dev = TpuAggregator(capacity=1 << 12, batch_size=CHUNK)
    sink = AggregatorSink(dev, flush_size=CHUNK)
    for start in range(0, N, CHUNK):
        sink.store_raw_batch(RawBatch(
            lis[start:start + CHUNK], eds[start:start + CHUNK], start,
            "parity-log"))
    sink.flush()
    sink.close()
    dev_snap = dev.drain()

    # (2) the exact host lane and (3) the rediscache path, entry by
    # entry from the same wire bytes.
    host = TpuAggregator(capacity=1 << 12, batch_size=CHUNK)
    server = MiniRedis().start()
    try:
        db = FilesystemDatabase(NoopBackend(), RedisCache(server.address))
        dsink = DatabaseSink(db)
        for j in range(N):
            e = decode_entry(j, base64.b64decode(lis[j]),
                             base64.b64decode(eds[j]))
            host._host_exact(
                e.cert_der, host.registry.get_or_assign(e.issuer_der))
            dsink.store(e, "parity-log")
        host_snap = host.drain()
        redis_counts, redis_serials = {}, set()
        for isd in db.get_issuer_and_dates_from_cache():
            for exp in isd.exp_dates:
                known = db.get_known_certificates(exp, isd.issuer)
                redis_counts[(isd.issuer.id(), exp.id())] = known.count()
                redis_serials |= {s.serial for s in known.known()}
    finally:
        server.stop()

    # Per-(issuer, expiry) counts equal across the three.
    assert dict(dev_snap.counts) == dict(host_snap.counts) == redis_counts
    assert dev_snap.total == host_snap.total == UNIQUE
    assert sum(redis_counts.values()) == UNIQUE
    assert sorted(dev_snap.issuers()) == sorted(host_snap.issuers())
    # Serial k is issuer k % 2's: an even split, each counted once.
    assert _per_issuer(dev_snap.counts) == [UNIQUE // 2, UNIQUE // 2]
    # The serials are generated, so the exact set redis holds is known.
    want = set()
    for serial in range(UNIQUE):
        tpl = tpls[serial % 2]
        der = syncerts.stamp_serial(tpl, serial)
        want.add(der[tpl.serial_off:tpl.serial_off + tpl.serial_len])
    assert redis_serials == want
    # The device path took the device lane: nothing spilled to the host.
    assert dev.metrics["host_lane"] == 0
