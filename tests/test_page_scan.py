"""A get-entries page as bytes (PR 30): the native scan that finds the
two base64 columns in a response body against ``json.loads``, the
page-returning client call against ``get_raw_entries``, and the whole
raw-batch path with the scan against the same path with every page
parsed as JSON.

The scanner's contract is differential: for any body it either yields
exactly the strings ``json.loads`` yields, or says "not mine" and the
page then parses, or raises, exactly as it always has.
"""

import base64
import datetime
import json

import numpy as np
import pytest

from ct_mapreduce_tpu.agg.aggregator import TpuAggregator
from ct_mapreduce_tpu.ingest import leaf as leaflib
from ct_mapreduce_tpu.ingest.ctclient import CTLogClient
from ct_mapreduce_tpu.ingest.sync import (
    AggregatorSink,
    LogSyncEngine,
    RawBatch,
)
from ct_mapreduce_tpu.native import available, leafpack, load
from ct_mapreduce_tpu.storage.certdb import FilesystemDatabase
from ct_mapreduce_tpu.storage.mockbackend import MockBackend
from ct_mapreduce_tpu.storage.mockcache import MockRemoteCache
from ct_mapreduce_tpu.telemetry import metrics, trace
from ct_mapreduce_tpu.utils import minicert
from tests.fakelog import FakeLog

needs_native = pytest.mark.skipif(not available(), reason="no C++ compiler")

NOW = datetime.datetime(2025, 1, 1, tzinfo=datetime.timezone.utc)
LI = ["AAAAAAF/abc+/w==", "QUJD", "Zm9vYmFy"]
ED = ["AAAA", "", "ZXh0cmE="]


@pytest.fixture(autouse=True)
def clean_telemetry():
    trace.disable()
    metrics.set_sink(metrics.InMemSink())
    yield
    trace.disable()
    metrics.set_sink(metrics.InMemSink())


def entries(lis=LI, eds=ED):
    return [{"leaf_input": li, "extra_data": ed} for li, ed in zip(lis, eds)]


def compact(obj) -> bytes:
    return json.dumps(obj, separators=(",", ":")).encode()


def body_of(lis=LI, eds=ED) -> bytes:
    return compact({"entries": entries(lis, eds)})


BODY = body_of()

# name -> (body, does the scanner take it)
CASES = {
    "compact": (BODY, True),
    "spaced": (json.dumps({"entries": entries()}).encode(), True),
    "indented": (json.dumps({"entries": entries()}, indent=2).encode(), True),
    "tabs_and_crlf": (
        b'\r\n\t{ "entries"\t:\r\n[ {"leaf_input" :\t"QUJD" ,\r\n'
        b'"extra_data": "AAAA"}\t]\r\n}\r\n ', True),
    "extra_data_first": (compact({"entries": [
        {"extra_data": ed, "leaf_input": li} for li, ed in zip(LI, ED)]}),
        True),
    "extra_members": (compact({"entries": [
        {"sct": "c2N0", "leaf_input": li, "note": "", "extra_data": ed,
         "z": "{[,:]}"} for li, ed in zip(LI, ED)]}), True),
    "top_level_string_member": (compact(
        {"note": "x y", "entries": entries(), "more": ""}), True),
    "extra_data_absent": (compact(
        {"entries": [{"leaf_input": li} for li in LI]}), True),
    "extra_data_absent_in_some": (compact({"entries": [
        {"leaf_input": "QUJD"}, {"leaf_input": "QUJE", "extra_data": "AAAA"},
        {"leaf_input": "QUJF"}]}), True),
    "empty_entries": (b'{"entries":[]}', True),
    "empty_entries_spaced": (b' { "entries" : [ ] } ', True),
    "one_entry": (body_of(LI[:1], ED[:1]), True),
    "slash_in_value": (body_of(["ab/d", "////"], ["/w==", "AA/A"]), True),
    "trailing_whitespace": (BODY + b"\n \t\r\n", True),
    "leading_whitespace": (b"\n\n  " + BODY, True),
    "value_of_length_0": (body_of(["", "QUJD"], ["", ""]), True),
    "value_of_1mb": (body_of(["QUJD" * (1 << 18), "QUJD"],
                             ["AAAA", "QUJD" * (1 << 18)]), True),
    "not_base64_but_plain": (body_of(["a b~c!", "{}[]:,"], ["#", " "]), True),
    "del_byte_in_value": (
        b'{"entries":[{"leaf_input":"AB\x7fC","extra_data":"AAAA"}]}', True),
    "other_key_repeated": (
        b'{"entries":[{"x":"1","leaf_input":"QUJD","x":"2"}]}', True),
    # -- not the scanner's: json.loads parses these --------------------
    "escaped_slash_in_value": (
        b'{"entries":[{"leaf_input":"ab\\/d","extra_data":"AAAA"}]}', False),
    "escaped_slash_in_extra_data": (
        b'{"entries":[{"leaf_input":"QUJD","extra_data":"AA\\/A"}]}', False),
    "escaped_quote_in_value": (
        b'{"entries":[{"leaf_input":"ab\\"d","extra_data":"AAAA"}]}', False),
    "unicode_escape_in_value": (
        b'{"entries":[{"leaf_input":"QU\\u004aD"}]}', False),
    "escape_in_key": (
        b'{"entries":[{"leaf\\u005finput":"QUJD","extra_data":"AAAA"}]}',
        False),
    "escape_in_other_member": (
        b'{"entries":[{"leaf_input":"QUJD","note":"a\\nb"}]}', False),
    "non_ascii_in_value": (
        '{"entries":[{"leaf_input":"QUé","extra_data":"✓AAA"}]}'.encode(),
        False),
    "non_ascii_in_other_member": (
        '{"entries":[{"leaf_input":"QUJD","note":"é"}]}'.encode(), False),
    "leaf_input_repeated": (
        b'{"entries":[{"leaf_input":"QUJD","leaf_input":"QUJE"}]}', False),
    "extra_data_repeated": (
        b'{"entries":[{"leaf_input":"QUJD","extra_data":"AAAA",'
        b'"extra_data":"AAAB"}]}', False),
    "entries_repeated": (
        b'{"entries":[{"leaf_input":"QUJD"}],'
        b'"entries":[{"leaf_input":"QUJE"}]}', False),
    "number_member": (
        b'{"entries":[{"leaf_input":"QUJD","index":7}]}', False),
    "null_bool_members": (
        b'{"entries":[{"leaf_input":"QUJD","a":null,"b":true,"c":false}]}',
        False),
    "nested_member": (
        b'{"entries":[{"leaf_input":"QUJD","sth":{"a":["b"]}}]}', False),
    "top_level_number_member": (
        b'{"count":3,"entries":[{"leaf_input":"QUJD"}]}', False),
    "no_entries_member": (b'{"error":"none"}', False),
    "empty_object": (b"{}", False),
    "utf8_bom": (b"\xef\xbb\xbf" + BODY, False),
    "utf16": (BODY.decode().encode("utf-16"), False),
    # -- not the scanner's: the parent's parse raises for these --------
    "trailing_bytes": (BODY + b"x", False),
    "trailing_brace": (BODY + b"}", False),
    "two_documents": (BODY + BODY, False),
    "empty_body": (b"", False),
    "only_whitespace": (b"  \n", False),
    "top_level_array": (b'[{"leaf_input":"QUJD"}]', False),
    "entries_an_object": (b'{"entries":{"leaf_input":"QUJD"}}', False),
    "entries_a_string": (b'{"entries":"QUJD"}', False),
    "entry_a_string": (b'{"entries":["QUJD"]}', False),
    "leaf_input_missing": (b'{"entries":[{"extra_data":"AAAA"}]}', False),
    "entry_an_empty_object": (b'{"entries":[{}]}', False),
    "leaf_input_a_number": (b'{"entries":[{"leaf_input":5}]}', False),
    "extra_data_null": (
        b'{"entries":[{"leaf_input":"QUJD","extra_data":null}]}', False),
    "control_byte_in_value": (
        b'{"entries":[{"leaf_input":"QU\nJD"}]}', False),
    "nul_byte_in_value": (b'{"entries":[{"leaf_input":"QU\x00JD"}]}', False),
    "trailing_comma_in_array": (
        b'{"entries":[{"leaf_input":"QUJD"},]}', False),
    "trailing_comma_in_entry": (
        b'{"entries":[{"leaf_input":"QUJD",}]}', False),
    "trailing_comma_at_top": (
        b'{"entries":[{"leaf_input":"QUJD"}],}', False),
    "missing_colon": (b'{"entries":[{"leaf_input" "QUJD"}]}', False),
    "missing_comma": (
        b'{"entries":[{"leaf_input":"QUJD"}{"leaf_input":"QUJE"}]}', False),
    "single_quotes": (b"{'entries':[]}", False),
    "unquoted_key": (b'{entries:[]}', False),
    "form_feed_as_whitespace": (b'{"entries":\x0c[]}', False),
    "invalid_utf8": (b'{"entries":[{"leaf_input":"QU\xff\xfeJD"}]}', False),
}
# A body cut short anywhere is malformed: cut the compact body inside a
# key, inside each value, between members, between entries, before each
# closing bracket.
CUTS = sorted({1, 2, 5, 10, 11, 12, 13, 14, 15, 26, 27, 28, 30, 44, 45, 46,
               58, 59, 60, 64, 65, 66, 67, 68, len(BODY) // 2,
               len(BODY) - 3, len(BODY) - 2, len(BODY) - 1})
for _cut in CUTS:
    CASES[f"cut_at_{_cut}"] = (BODY[:_cut], False)


def client_for(body: bytes) -> CTLogClient:
    return CTLogClient("ct.example.com/scan",
                       transport=lambda url: (200, {}, body))


def outcome(call):
    """What a parse gives: the two columns as ``str``, or what it
    raised."""
    try:
        got = call()
    except Exception as err:
        return ("raises", type(err))
    return ("parses",) + got


def parent_parse(body: bytes):
    """The per-entry client call, untouched by the page path: what the
    raw-batch path built its lists from before."""
    got = client_for(body).get_raw_entries(0, 999)
    return ([e.leaf_input for e in got], [e.extra_data for e in got])


def page_strings(page) -> tuple:
    return tuple([b.decode("utf-8", "surrogatepass") for b in col]
                 for col in page.items())


def counters() -> dict:
    return {k: v for k, v in metrics.get_sink().snapshot()["counters"].items()
            if k.startswith("ingest.page.")}


@needs_native
@pytest.mark.parametrize("name", sorted(CASES))
def test_scanner_yields_what_json_yields_or_declines(name):
    """The scan itself: a page whose every slice is the string
    ``json.loads`` returns, in order, or no page at all."""
    body, scanned = CASES[name]
    page = leafpack.scan_entries(body, 1000)
    assert (page is not None) == scanned
    if page is None:
        return
    want = json.loads(body)["entries"]
    assert len(page) == len(want)
    lis, eds = page_strings(page)
    assert lis == [e["leaf_input"] for e in want]
    assert eds == [e.get("extra_data", "") for e in want]
    assert page.body is body  # nothing copied
    for arr in (page.li_off, page.li_len, page.ed_off, page.ed_len):
        assert arr.dtype == np.int64 and arr.shape == (len(want),)


@pytest.mark.parametrize("name", sorted(CASES))
def test_page_call_parses_or_raises_as_the_per_entry_call_does(name):
    """Through the client: the page holds exactly the strings
    ``get_raw_entries`` returns, or the call raises. One span, one
    counter a page, and they say whether the scan took it."""
    body, scanned = CASES[name]
    scanned = scanned and available()
    want = outcome(lambda: parent_parse(body))
    if want[0] == "parses" and not all(
            isinstance(s, str) for col in want[1:] for s in col):
        # a value that is no string: the parent enqueued it and the
        # store thread's join raised TypeError; the page call raises it
        want = ("raises", TypeError)
    trace.enable(ring_size=64)
    got = outcome(lambda: page_strings(
        client_for(body).get_entry_page(0, 999)))
    spans = [e for e in trace.snapshot_events()
             if e["name"] == "fetch.parse_json"]
    assert got == want
    assert len(spans) == 1
    if got[0] == "parses":
        args = dict(spans[0]["args"])
        # A library that stamps its calls says how long the scan ran
        # and what the GIL cost on its return, taken or declined.
        stamps = {k: args.pop(k) for k in ("native_us", "gil_us") if k in args}
        assert args == {"n": len(got[1]), "scanned": int(scanned)}
        assert not scanned or (
            stamps["native_us"] >= 0 and stamps["gil_us"] >= 0)
        assert counters() == {
            "ingest.page.scanned" if scanned
            else "ingest.page.json_fallback": 1.0}
    else:
        assert counters() == {}


@needs_native
def test_library_without_the_scanner_takes_the_fallback(monkeypatch):
    """A prebuilt library from before the scanner (``has_scan`` false)
    parses every page as JSON, into the same page form."""
    monkeypatch.setattr(load(), "has_scan", False)
    assert leafpack.scan_entries(BODY, 1000) is None
    page = client_for(BODY).get_entry_page(0, 999)
    assert page_strings(page) == (LI, ED)
    assert page.body is not BODY
    assert counters() == {"ingest.page.json_fallback": 1.0}


@needs_native
def test_more_entries_than_asked_for_is_not_scanned():
    """The arrays hold what was asked for; a server that answers with
    more is parsed as JSON, and every entry arrives."""
    assert leafpack.scan_entries(BODY, 2) is None
    assert len(leafpack.scan_entries(BODY, 3)) == 3
    page = client_for(BODY).get_entry_page(10, 11)
    assert page_strings(page) == (LI, ED)
    assert counters() == {"ingest.page.json_fallback": 1.0}


def test_page_call_keeps_the_window_clamp_and_empty_range():
    """Same request shaping as ``get_raw_entries``: nothing fetched for
    an empty range, and a short page clamps the window."""
    log = FakeLog()
    log.max_batch = 4
    for li in ["QUJD"] * 10:
        log.entries.append({"leaf_input": li, "extra_data": ""})
    c = CTLogClient(log.url, transport=log.transport)
    assert len(c.get_entry_page(5, 4)) == 0
    assert len(c.get_entry_page(0, 9)) == 4 and c.page_size == 4
    assert len(c.get_entry_page(4, 9)) == 4
    assert len(c.get_entry_page(8, 9)) == 2 and c.page_size == 4
    snap = metrics.get_sink().snapshot()["counters"]
    assert snap["ingest.window_clamp"] == 1.0


def test_page_of_strings_keeps_non_ascii_as_utf8():
    page = leafpack.page_of_strings(["QUé", "QUJD"], ["✓", "\ud800"])
    assert page_strings(page) == (["QUé", "QUJD"], ["✓", "\ud800"])
    assert page.leaf_input(0) == "QUé".encode() and page.extra_data(1) == (
        "\ud800".encode("utf-8", "surrogatepass"))


# -- the whole raw-batch path, scanned against parsed ----------------------

PAGE, BATCH, N_ENTRIES = 8, 32, 104  # three whole batches and a tail
ISSUERS = [minicert.make_cert(serial=1 + k, issuer_cn=f"Scan CA {k}",
                              is_ca=True) for k in range(3)]
FUTURE = datetime.datetime(2031, 1, 1, tzinfo=datetime.timezone.utc)


def wire_entries() -> list[dict]:
    out = []
    for j in range(N_ENTRIES):
        k = (j * 7) % 3
        leaf = minicert.make_cert(
            serial=100 + j - (j % 13 == 5),  # a few duplicate serials
            issuer_cn=f"Scan CA {k}", subject_cn=f"s{j}.example",
            is_ca=False, not_after=FUTURE)
        li = base64.b64encode(leaflib.encode_leaf_input(leaf, 1000 + j))
        ed = base64.b64encode(leaflib.encode_extra_data([ISSUERS[k]]))
        out.append({"leaf_input": li.decode(), "extra_data": ed.decode()})
    out[17]["leaf_input"] = "!!notbase64!!"  # one entry no lane decodes
    out[40]["extra_data"] = ""  # and one with no chain
    return out


def run_engine(log_entries, compact_bodies: bool):
    """The engine as ct-fetch wires it on the TPU backend, every
    ``DecodedBatch`` the sink decoded on the way, the aggregate and the
    cursor."""
    log = FakeLog()
    log.max_batch = PAGE
    log.entries = log_entries
    transport = log.transport
    if compact_bodies:  # as real logs emit it; FakeLog spaces its JSON
        def transport(url):
            status, headers, body = log.transport(url)
            return status, headers, compact(json.loads(body))
    decoded = []
    orig = leafpack.decode_raw_pages

    def spy(pages, pad_len, workers=None, threads=None):
        decoded.append(orig(pages, pad_len, workers=workers, threads=threads))
        return decoded[-1]

    agg = TpuAggregator(capacity=1 << 12, batch_size=BATCH, now=NOW)
    sink = AggregatorSink(agg, flush_size=BATCH)
    db = FilesystemDatabase(MockBackend(), MockRemoteCache())
    engine = LogSyncEngine(sink, db, num_threads=1, raw_batches=True)
    leafpack.decode_raw_pages = spy
    try:
        engine.start_store_threads()
        engine.sync_log(log.url, transport=transport)
        engine.wait_for_downloads(timeout=120)
        engine.stop()
        sink.close()
    finally:
        leafpack.decode_raw_pages = orig
    assert not engine.errors, engine.errors
    state = db.get_log_state("ct.example.com/fake")
    snap = agg.drain()
    return decoded, snap, (state.max_entry, state.last_entry_time)


def assert_same_batches(got, want):
    assert len(got) == len(want)
    for a, b in zip(got, want):
        for fld in ("data", "length", "timestamp_ms", "entry_type",
                    "status", "issuer_group"):
            x, y = getattr(a, fld), getattr(b, fld)
            assert x.dtype == y.dtype and x.tobytes() == y.tobytes(), fld
        assert a.group_issuers == b.group_issuers


@needs_native
@pytest.mark.parametrize("compact_bodies", [True, False],
                         ids=["compact", "spaced"])
def test_engine_scanned_and_parsed_give_the_same_batches(
        compact_bodies, monkeypatch):
    """The same log through ``LogSyncEngine`` with the scan, and with
    the library's flag saying it has none: byte-identical decoded
    batches, the same aggregate, the same cursor."""
    wire = wire_entries()
    scanned = run_engine(wire, compact_bodies)
    assert counters() == {"ingest.page.scanned": float(N_ENTRIES // PAGE)}
    metrics.set_sink(metrics.InMemSink())
    monkeypatch.setattr(load(), "has_scan", False)
    parsed = run_engine(wire, compact_bodies)
    assert counters() == {"ingest.page.json_fallback":
                          float(N_ENTRIES // PAGE)}
    assert len(scanned[0]) == -(-N_ENTRIES // BATCH)
    assert_same_batches(scanned[0], parsed[0])
    for a, b in ((scanned[1], parsed[1]),):
        assert a.total == b.total and a.total > 0
        assert dict(a.counts) == dict(b.counts)
    assert scanned[2] == parsed[2] and scanned[2][0] == N_ENTRIES


@needs_native
def test_a_page_the_scanner_declines_rides_in_the_same_chunk():
    """A log that escapes ``/`` on some pages (legal JSON): those pages
    parse as before, the others are scanned, one chunk decodes both and
    gives what an all-parsed run gives."""
    wire = wire_entries()
    log = FakeLog()
    log.max_batch = PAGE
    log.entries = wire
    calls = {"n": 0}

    def escaping(url):
        status, headers, body = log.transport(url)
        if "get-entries" in url:
            calls["n"] += 1
            if calls["n"] % 3 == 0:
                body = body.replace(b"/", b"\\/")
        return status, headers, body

    agg = TpuAggregator(capacity=1 << 12, batch_size=BATCH, now=NOW)
    sink = AggregatorSink(agg, flush_size=BATCH)
    engine = LogSyncEngine(sink, FilesystemDatabase(
        MockBackend(), MockRemoteCache()), num_threads=1, raw_batches=True)
    engine.start_store_threads()
    engine.sync_log(log.url, transport=escaping)
    engine.wait_for_downloads(timeout=120)
    engine.stop()
    sink.close()
    assert not engine.errors, engine.errors
    pages = N_ENTRIES // PAGE
    assert counters() == {"ingest.page.scanned": float(pages - pages // 3),
                          "ingest.page.json_fallback": float(pages // 3)}
    mixed = agg.drain()
    _, want, _ = run_engine(wire, compact_bodies=False)
    assert mixed.total == want.total
    assert dict(mixed.counts) == dict(want.counts)


def chunk_pages(kinds):
    """The wire entries cut into pages of PAGE, each in the form its
    kind says: ``list`` (two lists of str), ``scan`` (a scanned body),
    ``join`` (the fallback's joined buffer)."""
    wire = wire_entries()[:PAGE * len(kinds)]
    pages = []
    for k, kind in enumerate(kinds):
        part = wire[k * PAGE:(k + 1) * PAGE]
        lis = [e["leaf_input"] for e in part]
        eds = [e["extra_data"] for e in part]
        if kind == "list":
            pages.append(leafpack.StrPage(lis, eds))
        elif kind == "scan":
            pages.append(leafpack.scan_entries(
                compact({"entries": part}), PAGE))
        else:
            pages.append(leafpack.page_of_strings(lis, eds))
    assert all(p is not None for p in pages)
    return pages


@needs_native
@pytest.mark.parametrize("stale", [False, True], ids=["in_place", "stale"])
@pytest.mark.parametrize("threads", [1, 3])
@pytest.mark.parametrize("kinds", [
    ("scan",) * 6, ("join",) * 6, ("scan", "list", "scan", "join", "list",
                                   "scan"), ("list", "scan") * 3])
def test_mixed_pages_decode_to_the_rows_all_lists_give(
        kinds, threads, stale, monkeypatch):
    """Whatever forms a chunk's pages have, the decoder sees the same
    entries in the same order: rows, statuses, timestamps and issuer
    groups equal the all-list chunk's and the pure-Python lane's."""
    if stale:  # a library that reads no pointer columns joins the chunk
        monkeypatch.setattr(load(), "has_strs", False)
    want = leafpack.decode_raw_pages(chunk_pages(("list",) * len(kinds)),
                                     2048, threads=1)
    got = leafpack.decode_raw_pages(chunk_pages(kinds), 2048,
                                    threads=threads)
    assert_same_batches([got], [want])
    lis, eds = leafpack._flatten(chunk_pages(kinds))
    assert_same_batches([got], [leafpack._decode_python(lis, eds, 2048)])
    assert (got.status != leafpack.OK).sum() == 2


@needs_native
def test_sink_takes_list_and_page_batches_in_one_chunk():
    """``store_raw_batch`` with a list-form batch between scanned pages:
    one chunk, the same aggregate as all-list; and a page-form batch
    whose lists are read (and edited) becomes list form."""
    wire = wire_entries()[:BATCH]

    def feed(forms):
        agg = TpuAggregator(capacity=1 << 12, batch_size=BATCH, now=NOW)
        sink = AggregatorSink(agg, flush_size=BATCH)
        for k, form in enumerate(forms):
            part = wire[k * PAGE:(k + 1) * PAGE]
            if form == "list":
                raw = RawBatch([e["leaf_input"] for e in part],
                               [e["extra_data"] for e in part],
                               k * PAGE, "log")
            else:
                raw = RawBatch(start_index=k * PAGE, log_url="log",
                               page=leafpack.scan_entries(
                                   compact({"entries": part}), PAGE))
                assert len(raw) == PAGE
                if form == "thawed":
                    raw.leaf_inputs[-1] = raw.leaf_inputs[0]
                    raw.extra_datas[-1] = raw.extra_datas[0]
                    assert isinstance(raw.page, leafpack.StrPage)
            sink.store_raw_batch(raw)
        sink.flush()
        sink.close()
        return agg.drain()

    want = feed(["list"] * 4)
    got = feed(["page", "list", "page", "page"])
    assert got.total == want.total > 0
    assert dict(got.counts) == dict(want.counts)
    # the edit is decoded: one entry seen twice, one never
    assert feed(["page", "thawed", "page", "page"]).total == want.total - 1


# -- a chunk of pages kept as bytes goes over as a page table (PR 39) ------

_WIRE: list = []


def wire_cycle(first: int, count: int) -> list[dict]:
    """``count`` wire entries from ``first`` on, the fixture's 104 taken
    round and round (the bad entry and the chainless one come by again
    every time)."""
    if not _WIRE:
        _WIRE.extend(wire_entries())
    return [_WIRE[(first + i) % N_ENTRIES] for i in range(count)]


def table_chunk(n_pages: int, shape: str) -> list:
    """A chunk whose every page is an ``EntryPage``, of uneven page
    sizes (1 to PAGE entries) so that no thread's lane range ends where
    a page does. ``shape``: ``scanned`` (every page a scanned body);
    ``empty_page`` (the second page, or the only one, has no entry);
    ``no_extra`` (the first page's entries have no ``extra_data``
    member: the scanner says length 0 at offset 0); ``scan_beside_join``
    (every other page laid out by ``page_of_strings``)."""
    pages, first = [], 0
    for k in range(n_pages):
        part = wire_cycle(first, 1 + (k * 5) % PAGE)
        first += len(part)
        if shape == "empty_page" and k == min(1, n_pages - 1):
            part = []
        if shape == "no_extra" and k == 0:
            part = [{"leaf_input": e["leaf_input"]} for e in part]
        if shape == "scan_beside_join" and k % 2:
            pages.append(leafpack.page_of_strings(
                [e["leaf_input"] for e in part],
                [e["extra_data"] for e in part]))
        else:
            pages.append(leafpack.scan_entries(
                compact({"entries": part}), PAGE))
        assert isinstance(pages[-1], leafpack.EntryPage)
        assert len(pages[-1]) == len(part)
    return pages


def page_counters() -> dict:
    return {k: v for k, v in metrics.get_sink().snapshot()["counters"].items()
            if k.startswith("decode.pages_")}


def decode_both_ways(pages, pad, threads, monkeypatch):
    """The chunk decoded from its page table, and by the columns built
    page by page as before PR 39 (the library's flag saying it takes no
    table); the counters say which way each went."""
    got = leafpack.decode_raw_pages(pages, pad, threads=threads)
    assert page_counters() == {"decode.pages_tabled": float(len(pages)),
                               "decode.pages_walked": 0.0}
    metrics.set_sink(metrics.InMemSink())
    with monkeypatch.context() as m:
        m.setattr(load(), "has_pages", False)
        want = leafpack.decode_raw_pages(pages, pad, threads=threads)
    assert page_counters() == {"decode.pages_tabled": 0.0,
                               "decode.pages_walked": float(len(pages))}
    metrics.set_sink(metrics.InMemSink())
    return got, want


@needs_native
@pytest.mark.parametrize("threads", [1, 3])
@pytest.mark.parametrize("shape", ["scanned", "empty_page", "no_extra",
                                   "scan_beside_join"])
@pytest.mark.parametrize("n_pages", [1, 2, 128, 300])
def test_page_table_decodes_to_the_bytes_the_walked_columns_give(
        n_pages, shape, threads, monkeypatch):
    """Byte for byte the same ``DecodedBatch`` (rows, lengths,
    timestamps, entry types, statuses, issuer groups in first-appearance
    order) whether the native call expands the chunk's page table or
    the store thread builds the pointer columns in NumPy; and, for the
    chunks small enough to ask it, what the pure-Python lane gives."""
    pages = table_chunk(n_pages, shape)
    got, want = decode_both_ways(pages, 2048, threads, monkeypatch)
    assert_same_batches([got], [want])
    assert got.issuers == want.issuers
    assert len(got.status) == sum(map(len, pages))
    if n_pages <= 2:
        lis, eds = leafpack._flatten(pages)
        assert_same_batches([got],
                            [leafpack._decode_python(lis, eds, 2048)])
    elif shape != "no_extra":  # the cycle's 17th and 40th came by
        assert {leafpack.OK, leafpack.BAD_B64, leafpack.NO_CHAIN} <= set(
            got.status.tolist())
    if shape == "no_extra":
        assert set(got.status[:len(pages[0])].tolist()) <= {
            leafpack.NO_CHAIN, leafpack.BAD_B64}


@needs_native
@pytest.mark.parametrize("threads", [1, 4])
def test_too_long_redecode_sees_the_same_maximum_from_the_table(
        threads, monkeypatch):
    """A precert whose certificate rides in ``extra_data`` past the
    narrow rows, in a chunk of scanned pages: the sink picks the narrow
    pad from the pages' stored maxima (no NumPy), the narrow decode says
    TOO_LONG, one full-width redecode follows: the pads and every
    decoded batch are what the walked columns give."""
    from tests import certgen

    issuer_der = ISSUERS[0]
    big = certgen.make_cert(
        serial=77, issuer_cn="Scan CA 0", subject_cn="pc.example.com",
        is_ca=False, not_after=FUTURE, extra_extensions=30,
        extra_ext_size=40)
    assert AggregatorSink.PAD_LEN // 2 < len(big) <= AggregatorSink.PAD_LEN
    pre = {"leaf_input": base64.b64encode(leaflib.encode_leaf_input(
               b"\x00" * 10, 7, entry_type=leaflib.PRECERT_ENTRY)).decode(),
           "extra_data": base64.b64encode(leaflib.encode_extra_data(
               [issuer_der], entry_type=leaflib.PRECERT_ENTRY,
               pre_certificate=big)).decode()}
    parts = [wire_cycle(0, PAGE), wire_cycle(PAGE, 3) + [pre],
             wire_cycle(PAGE + 3, PAGE)]

    def feed():
        seen = []
        orig = leafpack.decode_raw_pages

        def spy(pages, pad_len, workers=None, threads=None):
            seen.append((pad_len, orig(pages, pad_len, workers=workers,
                                       threads=threads)))
            return seen[-1][1]

        agg = TpuAggregator(capacity=1 << 12, batch_size=BATCH, now=NOW)
        sink = AggregatorSink(agg, flush_size=BATCH,
                              decode_threads=threads)
        with monkeypatch.context() as m:
            m.setattr(leafpack, "decode_raw_pages", spy)
            first = 0
            for part in parts:
                sink.store_raw_batch(RawBatch(
                    start_index=first, log_url="log",
                    page=leafpack.scan_entries(compact({"entries": part}),
                                               PAGE)))
                first += len(part)
            sink.flush()
            sink.close()
        return seen, agg.drain()

    got, got_snap = feed()
    assert page_counters() == {"decode.pages_tabled": 6.0,  # decoded twice
                               "decode.pages_walked": 0.0}
    monkeypatch.setattr(load(), "has_pages", False)
    want, want_snap = feed()
    narrow = AggregatorSink.PAD_LEN // 2
    assert [p for p, _ in got] == [p for p, _ in want] == [
        narrow, AggregatorSink.PAD_LEN]
    assert (got[0][1].status == leafpack.TOO_LONG).sum() == 1
    assert not (got[1][1].status == leafpack.TOO_LONG).any()
    assert_same_batches([d for _, d in got], [d for _, d in want])
    assert got_snap.total == want_snap.total > 0
    assert dict(got_snap.counts) == dict(want_snap.counts)


@needs_native
@pytest.mark.parametrize("kinds", [("scan", "list", "scan"),
                                   ("list",) * 3, ("join", "scan", "list")])
def test_a_str_page_in_the_chunk_takes_the_walk_and_counts_it(kinds):
    """One ``StrPage`` among the pages and the whole chunk's columns are
    built page by page as before: every page counts as walked, the span
    says so, and the batch is the all-list chunk's."""
    trace.enable(ring_size=64)
    try:
        got = leafpack.decode_raw_pages(chunk_pages(kinds), 2048, threads=2)
        (span,) = [e for e in trace.snapshot_events()
                   if e["name"] == "decode.concat_b64"]
    finally:
        trace.disable()
    assert page_counters() == {"decode.pages_tabled": 0.0,
                               "decode.pages_walked": float(len(kinds))}
    assert span["args"]["walked"] == span["args"]["pages"] == len(kinds)
    assert span["args"]["joined"] == 0
    want = leafpack.decode_raw_pages(chunk_pages(("list",) * len(kinds)),
                                     2048, threads=1)
    assert_same_batches([got], [want])


@needs_native
def test_the_span_of_a_tabled_chunk_keeps_its_arguments():
    """``decode.concat_b64`` [bytes, joined, pages, walked]: the same
    bytes as the walked columns add up to, nothing joined, nothing
    walked."""
    pages = table_chunk(5, "scan_beside_join")
    trace.enable(ring_size=64)
    try:
        leafpack.decode_raw_pages(pages, 2048, threads=1)
        (span,) = [e for e in trace.snapshot_events()
                   if e["name"] == "decode.concat_b64"]
    finally:
        trace.disable()
    cols, joined = leafpack._ptr_columns(load(), pages, sum(map(len, pages)))
    assert joined == 0
    assert {k: span["args"][k] for k in ("bytes", "joined", "pages",
                                         "walked")} == {
        "bytes": cols.nbytes, "joined": 0, "pages": 5, "walked": 0}
    assert cols.nbytes == sum(
        len(e["leaf_input"]) + len(e["extra_data"])
        for e in wire_cycle(0, sum(map(len, pages))))


@pytest.mark.parametrize("native_off", [False, True],
                         ids=["library", "CTMR_NATIVE=0"])
def test_no_library_counts_every_page_walked(native_off, monkeypatch):
    """The pure-Python lane takes the pages apart entry by entry: all
    walked, none tabled, and the same batch."""
    if native_off:
        monkeypatch.setenv("CTMR_NATIVE", "0")
    else:
        monkeypatch.setattr(leafpack, "load_native", lambda: None)
    pages = [leafpack.page_of_strings(
        [e["leaf_input"] for e in wire_cycle(k * PAGE, PAGE)],
        [e["extra_data"] for e in wire_cycle(k * PAGE, PAGE)])
        for k in range(3)]
    got = leafpack.decode_raw_pages(pages, 2048)
    assert page_counters() == {"decode.pages_tabled": 0.0,
                               "decode.pages_walked": 3.0}
    lis, eds = leafpack._flatten(pages)
    assert_same_batches([got], [leafpack._decode_python(lis, eds, 2048)])


def fuzzed_page_strings(rng) -> tuple[list, list]:
    n = int(rng.integers(0, 40))
    alphabet = np.frombuffer(
        b"ABCDEFGHIJKLMNOPQRSTUVWXYZabcdefghijklmnopqrstuvwxyz0123456789+/=",
        np.uint8)

    def text(limit: int) -> str:
        size = int(rng.integers(0, limit))
        return alphabet[rng.integers(0, len(alphabet), size)].tobytes(
            ).decode()

    return ([text(3000) for _ in range(n)],
            [text(5000) if rng.integers(3) else "" for _ in range(n)])


@needs_native
@pytest.mark.parametrize("stale", [False, True], ids=["scan", "stale"])
@pytest.mark.parametrize("seed", range(6))
def test_a_pages_stored_sizes_are_its_columns_maxima_and_sums(
        seed, stale, monkeypatch):
    """What the scan leaves in ``stats`` (and what a page computes for
    itself under a library from before PR 39, or when laid out from
    strings) is ``li_len.max()``, ``ed_len.max()`` and the two sums, on
    fuzzed pages: lengths 0 to 5,000, absent ``extra_data``, empty
    pages."""
    if stale:
        monkeypatch.setattr(load(), "has_pages", False)
    rng = np.random.default_rng(3900 + seed)
    for _ in range(25):
        lis, eds = fuzzed_page_strings(rng)
        ents = [{"leaf_input": li, **({"extra_data": ed} if ed else {})}
                for li, ed in zip(lis, eds)]
        for page in (leafpack.scan_entries(compact({"entries": ents}), 64),
                     leafpack.page_of_strings(lis, eds)):
            assert page_strings(page) == (lis, eds)
            assert page.stats == (
                max(map(len, lis), default=0), max(map(len, eds), default=0),
                sum(map(len, lis)), sum(map(len, eds)))
            assert page.stats == (
                int(page.li_len.max(initial=0)),
                int(page.ed_len.max(initial=0)),
                int(page.li_len.sum()), int(page.ed_len.sum()))
            assert all(type(v) is int for v in page.stats)
            assert page.max_leaf_input_len() == page.stats[0]
            assert page.row[0] == len(page) == len(lis)


@needs_native
def test_a_chunks_longest_leaf_input_is_a_max_over_stored_ints():
    """``_RawChunk.max_leaf_input_len``: the pages' stored numbers, for
    ``EntryPage`` and ``StrPage`` alike, and what NumPy said before."""
    from ct_mapreduce_tpu.ingest.sync import _RawChunk

    chunk = _RawChunk()
    assert chunk.max_leaf_input_len() == 0
    pages = table_chunk(7, "scan_beside_join") + chunk_pages(("list",))
    for k, page in enumerate(pages):
        chunk.add_page(RawBatch(start_index=100 * k, log_url="log",
                                page=page)
                       if isinstance(page, leafpack.EntryPage) else
                       RawBatch(page.leaf_inputs, page.extra_datas,
                                100 * k, "log"))
    lis, _ = leafpack._flatten(pages)
    assert chunk.max_leaf_input_len() == max(map(len, lis))
    assert type(chunk.max_leaf_input_len()) is int


@needs_native
def test_the_table_bounds_a_lane_ranges_extra_data_by_its_pages():
    """``_PageTable.ed_bytes``: never under the exact prefix-sum
    difference for any lane range, equal to it where the range is whole
    pages, and no more than the pages the range touches (empty pages
    among them cost nothing)."""
    pages = table_chunk(40, "empty_page") + [
        leafpack.page_of_strings([], [])] + table_chunk(9, "no_extra")
    n = sum(map(len, pages))
    table = leafpack._page_table(pages, n)
    exact, _ = leafpack._ptr_columns(load(), pages, n)
    assert (table.n, table.nbytes, table.longest) == (
        exact.n, exact.nbytes, exact.longest)
    assert table.rows.shape == (len(pages), 6) and table.starts[-1] == n
    rng = np.random.default_rng(39)
    for _ in range(400):
        lo, hi = sorted(int(x) for x in rng.integers(0, n + 1, 2))
        assert table.ed_bytes(lo, hi) >= exact.ed_bytes(lo, hi)
    for p in range(len(pages)):
        for q in range(p, len(pages)):
            lo, hi = table.starts[p], table.starts[q + 1]
            assert table.ed_bytes(lo, hi) == exact.ed_bytes(lo, hi)
    whole = table.ed_bytes(0, n)
    assert whole == exact.ed_bytes(0, n) == sum(p.stats[3] for p in pages)
    # one lane inside page 3 is bounded by that page alone
    mid = table.starts[3] + 1
    assert table.ed_bytes(mid, mid + 1) == pages[3].stats[3]
    assert leafpack._page_table(
        pages[:2] + chunk_pages(("list",)), 0) is None


@needs_native
def test_the_owner_keeps_a_chunks_pages_for_the_call_and_no_longer():
    """The table holds addresses, so what it points into must outlive
    the call: ``owner`` has the pages (each keeps its body and columns)
    until the columns object goes; after a whole decode nothing holds
    them."""
    import gc
    import weakref

    pages = table_chunk(6, "scan_beside_join")
    n = sum(map(len, pages))
    refs = [weakref.ref(p) for p in pages]
    cols = leafpack._b64_columns(load(), pages, n)
    assert isinstance(cols, leafpack._PageTable)
    del pages
    gc.collect()
    assert all(r() is not None for r in refs)  # not before
    span = leafpack._decode_native(
        load(), cols, 2048,
        (np.zeros((n, 2048), np.uint8), np.zeros((n,), np.int32),
         np.zeros((n,), np.int64), np.zeros((n,), np.int32),
         np.zeros((n,), np.int32)), 2)
    assert span is not None
    del cols
    gc.collect()
    assert all(r() is None for r in refs)  # after
    pages = table_chunk(6, "scanned")
    refs = [weakref.ref(p) for p in pages]
    dec = leafpack.decode_raw_pages(pages, 2048, threads=2)
    del pages
    gc.collect()
    assert all(r() is None for r in refs) and len(dec.status) > 0


def numpy_c_calls(fn) -> int:
    """How many calls into NumPy's C functions ``fn()`` makes on this
    thread (``sys.setprofile``'s ``c_call`` events whose callee is
    NumPy's, a function of the module or a method of an array)."""
    import sys

    seen = []

    def prof(_frame, event, arg):
        if event != "c_call":
            return
        owner = getattr(arg, "__self__", None)
        module = (getattr(arg, "__module__", None)
                  or type(owner).__module__ or "")
        if module.split(".")[0] == "numpy" or isinstance(
                owner, (np.ndarray, np.generic)):
            seen.append(arg)

    sys.setprofile(prof)
    try:
        fn()
    finally:
        sys.setprofile(None)
    return len(seen)


@needs_native
def test_numpy_calls_before_the_native_call_do_not_grow_with_the_pages(
        monkeypatch):
    """Between the cut and ``decode.native_call`` the store thread makes
    the same number of NumPy calls for 16 pages as for 256 when the
    chunk goes over as a table (each would hand the GIL round beside
    the downloaders); the walk it replaces makes some a page, which is
    also the proof that the profile sees them."""
    lib = load()
    small, large = table_chunk(16, "scanned"), table_chunk(256, "scanned")

    def calls(pages):
        n = sum(map(len, pages))
        return numpy_c_calls(lambda: leafpack._b64_columns(lib, pages, n))

    tabled = calls(small), calls(large)
    assert tabled[0] == tabled[1] <= 4, tabled
    monkeypatch.setattr(lib, "has_pages", False)
    walked = calls(small), calls(large)
    assert walked[1] - walked[0] >= 2 * (256 - 16), walked
