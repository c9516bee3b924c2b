"""The deployment ``icarus-cnfilter-1chip`` (ISSUE 52) against its plain
reference: ``issuerCNFilter`` through the walker lane, the pre-parsed
lane, the mesh aggregator and ``DatabaseSink``, on seeded entries over
the sixteen committed issuers (``benchmark/fixtures/templates.json``)
and a handful made here whose issuer Names are the ones the device's
scan and Go's ``pkix.Name`` could disagree on. ``reference_cn_filter.py``
imports nothing of the program; every lane has to give its serial sets,
its per-issuer counts, its three dropped counts and the three
``ct-fetch.certIsFilteredOut.*`` counters exactly.
"""

from __future__ import annotations

import base64
import datetime
import json
import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from ct_mapreduce_tpu.agg.aggregator import CN_PREFIX_ROWS, TpuAggregator
from ct_mapreduce_tpu.agg.sharded_agg import ShardedAggregator
from ct_mapreduce_tpu.core import packing
from ct_mapreduce_tpu.ingest.leaf import DecodedEntry
from ct_mapreduce_tpu.ingest.sync import DatabaseSink
from ct_mapreduce_tpu.native import available, leafpack
from ct_mapreduce_tpu.ops import der_kernel, pipeline
from ct_mapreduce_tpu.telemetry import metrics as tmetrics
from ct_mapreduce_tpu.telemetry import trace

import certgen

pytestmark = [certgen.requires_cryptography,
              # LONG_CN is past X.520's 64: cryptography says so each time.
              pytest.mark.filterwarnings("ignore:Attribute's length")]

UTC = datetime.timezone.utc
NOW = datetime.datetime(2026, 1, 1, tzinfo=UTC)
TEMPLATES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "fixtures", "templates.json")
BATCH = 128  # one walker shape for every test of this file
HEAD = "Bench Issuer CA 00"  # the cell's directive: the log's head issuer
LONG_CN = "L" * 80  # longer than the device's 61-byte window

# The directives: one prefix (the cell's), three, an empty element (which
# every name starts with), one of 70 bytes beside a short one, and one of
# 63 bytes that the long name does start with (the device sees 61).
DIRECTIVES = [
    HEAD,
    "Let's Encrypt,Bench Issuer CA 1,Other CA",
    HEAD + ",,x",
    "L" * 70 + ",Bench Issuer CA 07",
    "L" * 63,
]
COUNTERS = {"CA": "ct-fetch.certIsFilteredOut.CA",
            "expired": "ct-fetch.certIsFilteredOut.expired",
            "cn": "ct-fetch.certIsFilteredOut.cn"}


def made_names():
    """``(label, Name, the scan can say it)``: the issuer Names of ISSUE
    52's Tentpole 1(c). Where the third is False the device must hand
    the lane to the host lane (``filter.cn_undecidable``)."""
    from cryptography import x509
    from cryptography.x509.name import _ASN1Type
    from cryptography.x509.oid import NameOID

    def attr(oid, value, kind=_ASN1Type.UTF8String):
        return x509.NameAttribute(oid, value, _type=kind, _validate=False)

    def name(*rdns):
        return x509.Name([r if isinstance(r, x509.RelativeDistinguishedName)
                          else x509.RelativeDistinguishedName([r])
                          for r in rdns])

    cn, ou, org = (NameOID.COMMON_NAME, NameOID.ORGANIZATIONAL_UNIT_NAME,
                   NameOID.ORGANIZATION_NAME)
    return [
        ("no CN", name(attr(org, "An Org Without A Common Name")), True),
        # DER sorts a SET OF: the shorter OU comes first, the CN second.
        ("CN second in a multi-valued RDN", name(
            attr(org, "o"), x509.RelativeDistinguishedName(
                [attr(ou, "x"), attr(cn, HEAD + " multi")])), False),
        ("CN first in a multi-valued RDN", name(
            x509.RelativeDistinguishedName(
                [attr(cn, "Be"), attr(ou, "a long organisational unit")])),
         False),
        ("two CNs, the last passes", name(
            attr(cn, "Other first"), attr(org, "o"),
            attr(cn, HEAD + " last")), True),
        ("two CNs, the first passes", name(
            attr(cn, HEAD + " first"), attr(org, "o"),
            attr(cn, "Another CA last")), True),
        ("longer than the window", name(attr(cn, LONG_CN)), True),
        ("shorter than the prefix", name(attr(cn, "Bench Issuer")), True),
        ("PrintableString", name(
            attr(cn, HEAD + " printable", _ASN1Type.PrintableString)), True),
        ("TeletexString", name(
            attr(cn, HEAD + " teletex", _ASN1Type.T61String)), True),
        ("BMPString", name(
            attr(cn, HEAD + " bmp", _ASN1Type.BMPString)), False),
        ("thirteen RDNs, the CN last", name(
            *[attr(ou, f"unit {k}") for k in range(der_kernel.MAX_RDNS)],
            attr(cn, HEAD + " deep")), False),
    ]


def made_leaf(issuer_name, serial: int, key, *, is_ca=False,
              not_after=None) -> bytes:
    from cryptography import x509
    from cryptography.hazmat.primitives import hashes, serialization
    from cryptography.x509.oid import NameOID

    start = datetime.datetime(2025, 1, 1, tzinfo=UTC)
    builder = (
        x509.CertificateBuilder()
        .subject_name(x509.Name(
            [x509.NameAttribute(NameOID.COMMON_NAME, "leaf.example.com")]))
        .issuer_name(issuer_name).public_key(key.public_key())
        .serial_number(serial).not_valid_before(start)
        .not_valid_after(not_after or NOW + datetime.timedelta(days=900))
        .add_extension(x509.BasicConstraints(ca=is_ca, path_length=None),
                       critical=True))
    return builder.sign(key, hashes.SHA256()).public_bytes(
        serialization.Encoding.DER)


class Stream:
    """Seeded entries ``(leaf, CA certificate)``, each tagged with the
    made Name it carries (None: a committed issuer's)."""

    def __init__(self, seed: int, committed: int, per_made: int):
        from cryptography.hazmat.primitives.asymmetric import ec

        rng = np.random.default_rng(seed)
        with open(TEMPLATES) as fh:
            doc = json.load(fh)
        self.entries: list[tuple[bytes, bytes]] = []
        self.made_of: list[str | None] = []
        issuers = doc["issuers"]
        weights = 1.0 / np.arange(1, len(issuers) + 1) ** 1.1
        picks = rng.choice(len(issuers), size=committed,
                           p=weights / weights.sum())
        serials: list[int] = []
        for k in picks:
            issuer = issuers[int(k)]
            shape = issuer["leaves"][("rsa2048", "ec_p256")[
                int(rng.random() < 0.3)]]
            if serials and rng.random() < 0.05:  # a repeat
                serial = serials[int(rng.integers(len(serials)))]
            else:
                serial = int(rng.integers(1, 1 << 62))
                serials.append(serial)
            der = bytearray(base64.b64decode(shape["der"]))
            off = shape["serial_off"] + 1  # the first byte stays (sign)
            der[off:off + 15] = serial.to_bytes(15, "big")
            self.entries.append((bytes(der),
                                 base64.b64decode(issuer["issuer_der"])))
            self.made_of.append(None)
        key = ec.generate_private_key(ec.SECP256R1())
        past = NOW - datetime.timedelta(days=3)
        self.says = {}
        for j, (label, name, says) in enumerate(made_names()):
            ca = certgen.make_cert(issuer_cn=f"Made CA {j}", key_seed=j % 8,
                                   serial=9000 + j)
            self.says[label] = says
            for i in range(per_made):
                # One repeat, one CA certificate and one expired a Name:
                # the CA and expiry tests come before the CN's.
                serial = 7_000_000 + 100 * j + (0 if i == 1 else i)
                self.entries.append((made_leaf(
                    name, serial, key, is_ca=i == 2,
                    not_after=past if i == 3 else None), ca))
                self.made_of.append(label)
        order = rng.permutation(len(self.entries))
        self.entries = [self.entries[i] for i in order]
        self.made_of = [self.made_of[i] for i in order]

    def only(self, label: str) -> list[tuple[bytes, bytes]]:
        return [e for e, m in zip(self.entries, self.made_of) if m == label]


@pytest.fixture(scope="module")
def stream():
    return Stream(52, committed=1900, per_made=12)


def reference_of(entries, directive: str):
    import reference_cn_filter

    ref = reference_cn_filter.Reference(directive, NOW)
    why = [ref.feed(leaf, ca) for leaf, ca in entries]
    return ref, why


class Counters:
    """The process's metrics sink replaced by a fresh one for a run."""

    def __enter__(self):
        self.sink, self.prev = tmetrics.InMemSink(), tmetrics.get_sink()
        tmetrics.set_sink(self.sink)
        return self

    def __exit__(self, *exc):
        tmetrics.set_sink(self.prev)

    def __getitem__(self, key: str):
        return self.sink.snapshot()["counters"].get(key)


def prefixes_of(directive: str) -> tuple[str, ...]:
    return tuple(directive.split(",")) if directive else ()


def holds_the_reference(agg, results, ref, counters) -> None:
    """Serial sets, per-issuer counts, the three dropped counts and the
    three counters of one aggregator run, against the reference."""
    got: dict[str, set[bytes]] = {}
    for res in results:
        for i in np.flatnonzero(res.was_unknown):
            issuer = agg.registry.issuer_at(int(res.issuer_idx[i])).id()
            got.setdefault(issuer, set()).add(res.serials[i])
    assert got == ref.serials
    by_issuer: dict[str, int] = {}
    for (issuer, _exp), n in agg.drain().counts.items():
        by_issuer[issuer] = by_issuer.get(issuer, 0) + n
    assert by_issuer == ref.counts()
    assert {k: agg.metrics["filtered_" + k.lower()]
            for k in ref.dropped} == ref.dropped
    for k, key in COUNTERS.items():
        # .cn moves under a filter whatever it dropped; the other two
        # only when they drop.
        want = ref.dropped[k] or (0 if k == "cn" and agg.cn_prefixes else None)
        assert counters[key] == want, (key, counters[key], want)
    assert agg.metrics["parse_errors"] == 0


def undecidable_wanted(stream, made_of, why, directive) -> int:
    """Lanes that reach the CN test whose Name the scan cannot say, or
    that match the 61-byte head of a longer prefix they are long enough
    for: none where an empty prefix passes every name."""
    prefixes = prefixes_of(directive)
    if "" in prefixes:
        return 0
    n = 0
    for label, w in zip(made_of, why):
        if w in ("CA", "expired") or label is None:
            continue
        if not stream.says[label]:
            n += 1
        elif label == "longer than the window":
            n += not any(LONG_CN.startswith(p) for p in prefixes
                         if len(p) <= 61) and any(
                len(p) > 61 and LONG_CN[:61] == p[:61] and len(p) <= 80
                for p in prefixes)
    return n


def walker_run(agg, entries):
    return [agg.ingest(entries[lo:lo + 4 * BATCH])
            for lo in range(0, len(entries), 4 * BATCH)]


@pytest.mark.parametrize("directive", DIRECTIVES)
def test_the_walker_lane_is_the_reference(stream, directive):
    ref, why = reference_of(stream.entries, directive)
    assert all(ref.dropped.values()) or "" in prefixes_of(directive)
    agg = TpuAggregator(capacity=1 << 14, batch_size=BATCH, now=NOW,
                        cn_prefixes=prefixes_of(directive))
    with Counters() as counters:
        results = walker_run(agg, stream.entries)
        holds_the_reference(agg, results, ref, counters)
        undec = undecidable_wanted(stream, stream.made_of,
                                   why, directive)
        assert counters["filter.cn_undecidable"] == undec
        assert counters["filter.cn_passed"] + counters["filter.cn_dropped"] \
            + undec == sum(w in (None, "cn") for w in why)
        assert 0 <= counters["filter.cn_host_dropped"] <= undec
        assert counters["filter.cn_dropped"] \
            + counters["filter.cn_host_dropped"] == ref.dropped["cn"]
    assert agg.metrics["host_lane"] >= undec


@pytest.mark.skipif(not available(), reason="native library unavailable")
@pytest.mark.parametrize("directive", DIRECTIVES)
def test_the_preparsed_lane_is_the_reference(stream, directive):
    ref, why = reference_of(stream.entries, directive)
    agg = TpuAggregator(capacity=1 << 14, batch_size=BATCH, now=NOW,
                        cn_prefixes=prefixes_of(directive))
    with Counters() as counters:
        results = []
        for lo in range(0, len(stream.entries), 4 * BATCH):
            chunk = stream.entries[lo:lo + 4 * BATCH]
            batch = packing.pack_entries(
                [(leaf, agg.registry.get_or_assign(ca)) for leaf, ca in chunk],
                pad_len=1536)
            sidecar = leafpack.extract_sidecars(batch.data, batch.length)
            assert sidecar.ok.all()
            results.append(agg.ingest_preparsed(
                sidecar, batch.issuer_idx, batch.valid, batch.data,
                batch.length))
        holds_the_reference(agg, results, ref, counters)
        undec = undecidable_wanted(stream, stream.made_of,
                                   why, directive)
        assert counters["filter.cn_undecidable"] == undec
        assert counters["filter.cn_dropped"] \
            + counters["filter.cn_host_dropped"] == ref.dropped["cn"]


@pytest.mark.parametrize("directive", DIRECTIVES[:1] + DIRECTIVES[3:])
def test_the_mesh_aggregator_is_the_reference(stream, directive):
    devices = np.array(jax.devices()[:8])
    assert devices.size == 8, "conftest must provide 8 virtual devices"
    entries = stream.entries[:5 * 64]
    ref, why = reference_of(entries, directive)
    agg = ShardedAggregator(Mesh(devices, ("shard",)), capacity=1 << 13,
                            batch_size=64, now=NOW,
                            cn_prefixes=prefixes_of(directive))
    with Counters() as counters:
        results = [agg.ingest(entries[lo:lo + 64])
                   for lo in range(0, len(entries), 64)]
        holds_the_reference(agg, results, ref, counters)
        assert counters["filter.cn_undecidable"] == undecidable_wanted(
            stream, stream.made_of[:len(entries)], why, directive)
        assert counters["filter.cn_dropped"] \
            + counters["filter.cn_host_dropped"] == ref.dropped["cn"]


class _Stored:
    def __init__(self):
        self.pairs: list[tuple[bytes, bytes]] = []

    def store(self, cert_der, issuer_der, log_url, index) -> None:
        self.pairs.append((cert_der, issuer_der))


@pytest.mark.parametrize("directive", DIRECTIVES)
def test_database_sink_is_the_reference(stream, directive):
    import reference_cn_filter

    ref, _why = reference_of(stream.entries, directive)
    db = _Stored()
    sink = DatabaseSink(db, cn_filters=prefixes_of(directive), now=NOW)
    with Counters() as counters:
        for i, (leaf, ca) in enumerate(stream.entries):
            sink.store(DecodedEntry(index=i, timestamp_ms=0, entry_type=0,
                                    cert_der=leaf, issuer_der=ca), "log")
        got: dict[str, set[bytes]] = {}
        for leaf, ca in db.pairs:
            got.setdefault(reference_cn_filter.issuer_id(ca), set()).add(
                reference_cn_filter.raw_serial(leaf))
        assert got == ref.serials
        for k, key in COUNTERS.items():
            assert counters[key] == (ref.dropped[k] or None), key
        # This sink decides every lane itself: no filter. family.
        assert counters["filter.cn_dropped"] is None


def test_a_name_the_device_cannot_say_takes_the_host_lane(stream):
    """Each made Name alone through the walker lane under the cell's
    directive: where the scan cannot say what Go's pkix.Name would hold,
    every lane that reaches the CN test is handed to the host lane and
    decided there; where it can, none is, and the verdict is Go's."""
    agg = TpuAggregator(capacity=1 << 14, batch_size=BATCH, now=NOW,
                        cn_prefixes=(HEAD,))
    for label, says in stream.says.items():
        entries = stream.only(label)
        ref, why = reference_of(entries, HEAD)
        reached = sum(w in (None, "cn") for w in why)
        assert reached == len(entries) - 2  # less the CA and the expired
        before = dict(agg.metrics)
        with Counters() as counters:
            res = agg.ingest(entries)
            assert counters["filter.cn_undecidable"] == (
                0 if says else reached), label
            assert counters["filter.cn_host_dropped"] == (
                0 if says else ref.dropped["cn"]), label
            assert counters[COUNTERS["cn"]] == ref.dropped["cn"], label
        assert agg.metrics["host_lane"] - before["host_lane"] == (
            0 if says else reached), label
        assert int(res.was_unknown.sum()) == sum(
            len(s) for s in ref.serials.values()), label


def test_the_scan_says_what_go_would_hold_or_that_it_cannot(stream):
    """``parse_certs`` against ``cryptography``'s parse, Name by Name."""
    import reference_cn_filter
    from cryptography import x509

    leaves = [stream.only(label)[0][0] for label in stream.says]
    leaves.append(stream.entries[stream.made_of.index(None)][0])
    batch = packing.pack_entries([(d, 0) for d in leaves], pad_len=1536)
    out = der_kernel.parse_certs(batch.data, batch.length)
    for i, (der, label) in enumerate(zip(leaves, [*stream.says, None])):
        assert bool(out.ok[i])
        want = reference_cn_filter.go_common_name(
            x509.load_der_x509_certificate(der).issuer).encode()
        off, n = int(out.issuer_cn_off[i]), int(out.issuer_cn_len[i])
        if label is not None and not stream.says[label]:
            assert n == -1, label
        else:
            assert der[off:off + n] == want, label


def test_the_references_reader_of_basic_constraints_is_cryptographys(stream):
    """``reference_cn_filter.basic_constraints_ca`` stands in where
    ``cryptography`` refuses a committed template's other extensions;
    wherever both read, they agree, and the templates are leaves."""
    import reference_cn_filter
    from cryptography import x509

    seen = set()
    for (leaf, _ca), label in zip(stream.entries, stream.made_of):
        cert = x509.load_der_x509_certificate(leaf)
        mine = reference_cn_filter.basic_constraints_ca(
            cert.tbs_certificate_bytes)
        if label is None:
            assert mine is False
            continue
        theirs = cert.extensions.get_extension_for_class(
            x509.BasicConstraints).value.ca
        assert mine is theirs
        seen.add(theirs)
    assert seen == {True, False}


def test_one_filtered_program_whatever_the_directive_says():
    """Filter off is the step it was (its own shape, no predicate's
    outputs); filter on is ONE more jit entry a batch shape, which a
    directive of another length or count hits again."""
    entries = Stream(7, committed=BATCH, per_made=0).entries
    step = pipeline.ingest_step

    def run(directive: str) -> TpuAggregator:
        agg = TpuAggregator(capacity=1 << 12, batch_size=BATCH, now=NOW,
                            cn_prefixes=prefixes_of(directive))
        agg.ingest(entries)
        return agg

    off = run("")
    assert off._prefix_arr.shape == (0, 1) and off._prefix_lens.shape == (0, 2)
    size_off = step._cache_size()
    first = run(HEAD)
    assert first._prefix_arr.shape == (CN_PREFIX_ROWS,
                                       der_kernel.MAX_FIXED_WINDOW_BYTES)
    assert (first._prefix_lens[1:] == -1).all()
    size_on = step._cache_size()
    assert size_on <= size_off + 1
    for directive in ("Let's Encrypt", DIRECTIVES[1], "L" * 70 + ",,x"):
        again = run(directive)
        assert again._prefix_arr.shape == first._prefix_arr.shape
        assert step._cache_size() == size_on, directive
    run("")
    assert step._cache_size() == size_on
    # Nine prefixes take the next power of two: another shape, and said.
    nine = TpuAggregator(capacity=1 << 12, batch_size=BATCH, now=NOW,
                         cn_prefixes=tuple(f"p{k}" for k in range(9)))
    assert nine._prefix_arr.shape[0] == 16


def test_the_filter_counters_every_batch_and_none_without_a_filter():
    entries = Stream(9, committed=2 * BATCH, per_made=0).entries
    names = ("filter.cn_passed", "filter.cn_dropped",
             "filter.cn_undecidable", "filter.cn_host_dropped")

    class Every(tmetrics.InMemSink):
        def __init__(self):
            super().__init__()
            self.seen: list[tuple[str, float]] = []

        def incr_counter(self, key, value):
            self.seen.append((key, value))
            super().incr_counter(key, value)

    def run(directive: str):
        sink, prev = Every(), tmetrics.get_sink()
        tmetrics.set_sink(sink)
        try:
            agg = TpuAggregator(capacity=1 << 12, batch_size=BATCH, now=NOW,
                                cn_prefixes=prefixes_of(directive))
            agg.registry.get_or_assign(entries[0][1])
            for lo in (0, BATCH):
                batch = packing.pack_entries(
                    [(leaf, agg.registry.get_or_assign(ca))
                     for leaf, ca in entries[lo:lo + BATCH]], pad_len=1536)
                agg.ingest_packed(batch.data, batch.length, batch.issuer_idx,
                                  batch.valid)
        finally:
            tmetrics.set_sink(prev)
        return sink.seen

    # Nothing of the sixteen starts with this: two batches of drops, and
    # the counters that read 0 are said all the same. The fold's span
    # carries the batch's drops.
    tracer, prev = trace.SpanTracer(ring_size=256), trace._tracer
    trace._tracer = tracer
    try:
        seen = run("No Such CA")
    finally:
        trace._tracer = prev
    folds = [e for e in tracer.events() if e.get("name") == "device.fold"]
    assert [e["args"]["filtered_cn"] for e in folds] == [BATCH] * 2
    for name in names:
        assert [v for k, v in seen if k == name] == (
            [float(BATCH)] * 2 if name == "filter.cn_dropped" else [0.0] * 2)
    assert [v for k, v in seen if k == COUNTERS["cn"]] == [float(BATCH)] * 2
    off = run("")
    assert not [k for k, _ in off if k.startswith(("filter.", COUNTERS["cn"]))]
