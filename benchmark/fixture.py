"""The benchmark's inputs and the arithmetic its answers are held to.

Nothing here imports the program. A run's logs are made from ``--seed``
alone: which entries repeat an earlier serial, which issuer signs each
serial (Zipf over the issuers of ``fixtures/templates.json``), which
leaf shape carries it. The expected report follows from the same
arrays: N - D unique serials and the per-issuer counts. Where the
traffic file has a ``table_prefill`` block the table starts with the
standing rows of ``prefill.py`` (a fixed data set, not the seed's), the
seed also decides which entries repeat one of them, and the report
holds the standing rows beside the logs' own.

Copied from, and owed to, ``chip_smoke.py::Fixture`` and
``ct_mapreduce_tpu/utils/syncerts.py`` (``make_wire_batch``,
``stamp_serial``, ``zipf_weights``); the wire encoding is RFC 6962's
``MerkleTreeLeaf`` / ``X509ChainEntry`` written out here.
"""

from __future__ import annotations

import base64
import binascii
import json
import os
from dataclasses import dataclass

import numpy as np

import prefill

HERE = os.path.dirname(os.path.abspath(__file__))
BATCH = 65536  # entries per device dispatch: the one compiled program
TS_BASE_MS = 1_700_000_000_000
# Serial spaces, disjoint by construction: log k's entry i carries
# serial k * LOG_STRIDE + i (or that of the earlier entry it repeats).
LOG_STRIDE = 1 << 32
KNOWN_STREAM = 0x5EED  # the stream that draws the repeats of standing rows


zipf_weights = prefill.zipf_weights


def quantile(values: list[float], q: float) -> float:
    """Nearest-rank quantile of a non-empty list."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, int(np.ceil(q * len(s))) - 1))]


def window_entries(rate_per_s: float, seconds: float, logs: int = 1,
                   batch: int = BATCH) -> int:
    """Entries of one run's window: whole batches, at least two, and the
    same number of whole batches in every log."""
    batches = max(2, round(rate_per_s * seconds / batch))
    batches = -(-batches // logs) * logs
    return batches * batch


class Templates:
    """The committed certificates: per issuer a CA and one leaf per
    shape, each leaf with the offset of its 16 serial bytes."""

    def __init__(self, path: str | None = None):
        with open(path or os.path.join(HERE, "fixtures", "templates.json")) as fh:
            doc = json.load(fh)
        self.serial_len = int(doc["serial_len"])
        self.exp_date_id = doc["exp_date_id"]
        self.not_after = doc["not_after"]
        self.issuer_ids = [i["issuer_id"] for i in doc["issuers"]]
        self.kinds = sorted(doc["issuers"][0]["leaves"])
        self.leaf: list[dict[str, tuple[bytes, bytes]]] = []
        # Per issuer, what follows an entry's leaf in a get-entries
        # response: the chain (the CA alone) and the closing brace.
        self.entry_tail: list[bytes] = []
        for issuer in doc["issuers"]:
            shapes = {}
            for kind, spec in issuer["leaves"].items():
                der = base64.b64decode(spec["der"])
                off = int(spec["serial_off"])
                # MerkleTreeLeaf after the timestamp: entry_type x509,
                # 24-bit length, the DER up to the serial's first byte
                # (0x4D, kept) ... and after the serial, plus the empty
                # extensions.
                head = (b"\x00\x00" + len(der).to_bytes(3, "big")
                        + der[:off + 1])
                tail = der[off + self.serial_len:] + b"\x00\x00"
                shapes[kind] = (head, tail)
            self.leaf.append(shapes)
            ca = base64.b64decode(issuer["issuer_der"])
            chain = len(ca).to_bytes(3, "big") + ca
            self.entry_tail.append(
                b'","extra_data":"' + base64.b64encode(
                    len(chain).to_bytes(3, "big") + chain) + b'"}')


@dataclass
class LogSpec:
    """What the traffic file's ``log_replay`` block fixes."""

    logs: int
    page: int
    dup_share: float
    leaf_mix: dict
    issuers: int
    zipf_s: float
    warmup_entries: int
    window_entries: int  # the measured window's, over all logs
    ramp_entries: int = 0  # served before them in the same round
    tail_entries: int = 0  # and after them: the pipeline stays full
    # {"slots_log2", "load", "known_share"}: the table starts with the
    # standing rows of prefill.py, and known_share of the entries that
    # repeat no earlier entry of their log repeat one of those rows.
    table_prefill: dict | None = None

    @property
    def per_log(self) -> int:
        return (self.ramp_entries + self.window_entries
                + self.tail_entries) // self.logs

    @property
    def standing(self) -> "prefill.Standing | None":
        if self.table_prefill is None:
            return None
        return prefill.Standing.of(self.table_prefill, self.issuers,
                                   self.zipf_s)


class LogFixture:
    """One log of a run. Entries ``[0, warm)`` are the warm-up prefix
    (log 0 only), a round of its own; entries ``[warm, warm + n)`` are
    the next round's: its ramp, the measured window, its tail."""

    def __init__(self, spec: LogSpec, seed: int, index: int):
        self.spec = spec
        self.index = index
        self.warm = spec.warmup_entries if index == 0 else 0
        self.n = spec.per_log
        total = self.warm + self.n
        rng = np.random.default_rng([int(seed), index])
        is_dup = rng.random(total) < spec.dup_share
        is_dup[0] = False
        if self.warm:
            # The window's first entry is an original too, so that a
            # window's duplicates repeat window entries or warm-up
            # entries but the counts below stay simple to state.
            is_dup[self.warm] = False
        originals = np.flatnonzero(~is_dup)
        dups = np.flatnonzero(is_dup)
        n_earlier = np.searchsorted(originals, dups)
        at = np.arange(total, dtype=np.int64)
        at[dups] = originals[(rng.random(len(dups)) * n_earlier).astype(np.int64)]
        self.is_dup = is_dup
        self.serial_of = at + index * LOG_STRIDE
        # Issuer and leaf shape belong to the serial, so a repeated
        # serial is a true duplicate: same issuer, same bytes but the
        # timestamp.
        issuer_by_entry = rng.choice(
            spec.issuers, size=total,
            p=zipf_weights(spec.issuers, spec.zipf_s)).astype(np.int16)
        kinds = sorted(spec.leaf_mix)
        shares = np.array([spec.leaf_mix[k] for k in kinds], np.float64)
        kind_by_entry = rng.choice(
            len(kinds), size=total, p=shares / shares.sum()).astype(np.int8)
        self.kinds = kinds
        self.kind_of = kind_by_entry[at]
        # The standing row an entry repeats, or -1. Drawn from a stream
        # of its own, after everything else: a traffic file without the
        # block gives the arrays it always gave.
        self.standing_of = None
        standing = spec.standing
        if standing is not None:
            row_of = self._standing_rows(standing, seed)
            known = row_of >= 0
            issuer_by_entry = issuer_by_entry.copy()
            issuer_by_entry[known] = standing.issuer_of(row_of[known])
            self.standing_of = row_of[at]
        self.issuer_of = issuer_by_entry[at]

    def _standing_rows(self, standing, seed: int) -> np.ndarray:
        """Per entry that repeats no earlier entry of its log: the
        standing row it repeats (``known_share`` of them), else -1. No
        row is repeated twice in a run: the q-th such entry of the run
        (log by log, a fixed stride a log) takes row ``(a q + b) mod
        rows``, ``a`` prime to ``rows`` and both from the seed alone, so
        every log of the run draws from the one permutation."""
        spec, total, rows = self.spec, self.total, standing.rows
        stride = spec.warmup_entries + spec.per_log
        if not spec.logs * stride <= rows < 1 << 31:
            raise ValueError(f"{spec.logs * stride} entries cannot each "
                             f"repeat a row of their own of {rows}")
        shared = np.random.default_rng([int(seed), KNOWN_STREAM])
        a = int(shared.integers(1, rows)) | 1
        while np.gcd(a, rows) != 1:
            a += 2
        b = int(shared.integers(0, rows))
        mine = np.random.default_rng([int(seed), self.index, KNOWN_STREAM])
        known = ~self.is_dup & (
            mine.random(total) < spec.table_prefill["known_share"])
        q = self.index * stride + np.flatnonzero(known)
        out = np.full(total, -1, np.int64)
        out[known] = (a * q + b) % rows  # a, q < rows < 2^31: no overflow
        return out

    @property
    def total(self) -> int:
        return self.warm + self.n

    def unique_by_issuer(self, lo: int, hi: int) -> np.ndarray:
        """Serials first seen in entries ``[lo, hi)`` that the standing
        table does not hold, per issuer."""
        first = ~self.is_dup[lo:hi]
        if self.standing_of is not None:
            first &= self.standing_of[lo:hi] < 0
        return np.bincount(self.issuer_of[lo:hi][first],
                           minlength=self.spec.issuers)

    def page_body(self, tpl: Templates, start: int, end: int) -> bytes:
        """A get-entries response for ``[start, end]``, cut to a page."""
        end = min(end, start + self.spec.page - 1, self.total - 1)
        slen = tpl.serial_len - 1
        leaves = [[shapes[k] for k in self.kinds] for shapes in tpl.leaf]
        serials = self.serial_of[start:end + 1].tolist()
        if self.standing_of is not None:
            serials = [s if j < 0 else prefill.SERIAL_BASE + j for s, j in zip(
                serials, self.standing_of[start:end + 1].tolist())]
        rows = zip(self.issuer_of[start:end + 1].tolist(),
                   self.kind_of[start:end + 1].tolist(), serials)
        parts = []
        for ts, (issuer, kind, serial) in enumerate(rows, TS_BASE_MS + start):
            head, tail = leaves[issuer][kind]
            parts.append(b'{"leaf_input":"' + binascii.b2a_base64(b"".join((
                b"\x00\x00", ts.to_bytes(8, "big"), head,
                serial.to_bytes(slen, "big"), tail)), newline=False)
                + tpl.entry_tail[issuer])
        return b'{"entries":[' + b",".join(parts) + b"]}"


class RunFixture:
    """All logs of one run and what the report must say after it."""

    def __init__(self, spec: LogSpec, seed: int):
        self.spec = spec
        self.seed = int(seed)
        self.logs = [LogFixture(spec, seed, k) for k in range(spec.logs)]

    @property
    def offered(self) -> int:
        return sum(log.total for log in self.logs)

    @property
    def duplicates(self) -> int:
        return int(sum(log.is_dup.sum() for log in self.logs))

    def new_by_issuer(self) -> np.ndarray:
        """The logs' first sightings that no standing row holds."""
        return sum(log.unique_by_issuer(0, log.total) for log in self.logs)

    def standing_by_issuer(self) -> np.ndarray:
        standing = self.spec.standing
        if standing is None:
            return np.zeros(self.spec.issuers, np.int64)
        return standing.by_issuer()

    def expected_by_issuer(self) -> np.ndarray:
        return self.standing_by_issuer() + self.new_by_issuer()

    def expected_unique(self) -> int:
        return int(self.expected_by_issuer().sum())
