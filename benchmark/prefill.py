"""The standing table: the rows a restarted tailer's checkpoint holds
before the run's first page, written as a base checkpoint in the
program's on-disk format (a packed ``.npz`` beside its CTMRCK02
manifest, as a full save leaves them since PR 42).

Nothing here imports the program. What the format needs is restated:

- the **fingerprint** of a row: SHA-256 over ``expHour(4 B, big
  endian) | issuerIdx(4 B) | serialLen(1 B) | serial``, the digest's
  last 16 bytes as four big-endian words
  (``core/packing.py::fingerprint_message`` / ``fingerprint_host``);
- the **meta word**: ``issuerIdx << 18 | expHour - 400000``;
- the **table**: buckets of 24 slots, a power of two of them, at least
  ``2^slots_log2 / 24`` (``ops/buckettable.py::bucket_count``); a key's
  home bucket is ``(w0 ^ w1 * 0x9E3779B9) & (buckets - 1)``; a row that
  finds its bucket full lives in the next bucket that is not, and every
  bucket it passed is full (what ``contains`` walks);
- the **file**: ``fill`` (a byte a bucket), ``keys`` and ``meta`` of the
  occupied slots in bucket order, stored; ``count``, ``layout``,
  ``n_shards``, ``base_hour``, ``registry`` (issuer ids by index),
  ``issuer_totals``, the verify vectors and the empty host sets,
  deflated (``agg/aggregator.py::_write_npz``, ``agg/ckpt.py::write_npz``).

The standing table is a fixed data set, as ``fixtures/templates.json``
is: row ``j`` carries serial ``SERIAL_BASE + j`` (no log's
``k * LOG_STRIDE + i`` comes near), the templates' one expiry hour, and
an issuer by ranges of ``j`` in the Zipf shares of the traffic file,
so that a run's arithmetic needs no pass over the rows. It does not
depend on ``--seed``. ``load`` is a share of the slots the program's
table really has for ``tableBits = slots_log2`` (24 x the power of two
of buckets: 201,326,592 at 27), which is what its gauge
``aggregator.table_load`` and its growth policy divide by.

The file is built in the first run of a checkout and kept in
``.bench_cache/prefill/`` under a name that holds every parameter and a
hash of this source; a run links it to where ``aggStatePath`` points.
"""

from __future__ import annotations

import calendar
import hashlib
import json
import multiprocessing
import os
import shutil
import time
import zipfile
from concurrent.futures import ProcessPoolExecutor
from dataclasses import dataclass

import numpy as np

SLOTS = 24  # slots a bucket
SERIAL_BASE = 1 << 100  # row j's serial: 15 bytes after the templates' 0x4D
SERIAL_LEAD = 0x4D  # the first serial byte every template keeps
BASE_HOUR = 400_000  # the meta word's epoch-hour base (DEFAULT_BASE_HOUR)
META_HOUR_BITS = 18
MAX_ISSUERS = 1 << 14
MANIFEST_SUFFIX = ".ckmanifest.json"
CHUNK = 1 << 20  # rows a worker hashes at a time


def table_slots(slots_log2: int) -> int:
    """Slots of the table the program builds for ``tableBits``."""
    need = -(-(1 << slots_log2) // SLOTS)
    return SLOTS * (1 << max(0, need - 1).bit_length())


def zipf_weights(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s
    return w / w.sum()


def exp_hour_of(not_after: str) -> int:
    """``2031-06-15T14:00:00Z`` -> hours since the epoch."""
    return calendar.timegm(time.strptime(
        not_after, "%Y-%m-%dT%H:%M:%SZ")) // 3600


@dataclass(frozen=True)
class Standing:
    """The standing table's parameters and the arithmetic that needs no
    pass over its rows."""

    slots_log2: int
    load: float
    issuers: int
    zipf_s: float

    @classmethod
    def of(cls, block: dict, issuers: int, zipf_s: float) -> "Standing":
        return cls(int(block["slots_log2"]), float(block["load"]),
                   int(issuers), float(zipf_s))

    @property
    def rows(self) -> int:
        return round(self.load * table_slots(self.slots_log2))

    def by_issuer(self) -> np.ndarray:
        """Rows of each issuer: the Zipf shares of ``rows``, the largest
        remainders rounded up."""
        exact = zipf_weights(self.issuers, self.zipf_s) * self.rows
        counts = np.floor(exact).astype(np.int64)
        short = self.rows - int(counts.sum())
        counts[np.argsort(-(exact - counts), kind="stable")[:short]] += 1
        return counts

    def issuer_of(self, j: np.ndarray) -> np.ndarray:
        """Row ``j``'s issuer: rows lie in ranges, an issuer each."""
        return np.searchsorted(np.cumsum(self.by_issuer()), j,
                               side="right").astype(np.int16)


def source_hash() -> str:
    with open(os.path.abspath(__file__), "rb") as fh:
        return hashlib.sha256(fh.read()).hexdigest()[:12]


def cache_name(standing: Standing, issuer_ids: list[str], exp_hour: int,
               source: str | None = None) -> str:
    """Every parameter, the registry the rows are numbered by, and the
    writer's source: change any and the name moves."""
    ids = hashlib.sha256(json.dumps(
        issuer_ids[: standing.issuers]).encode()).hexdigest()[:8]
    return (f"standing-b{standing.slots_log2}-l{standing.load!r}"
            f"-i{standing.issuers}-z{standing.zipf_s!r}-h{exp_hour}"
            f"-r{ids}-w{source or source_hash()}.npz")


def hash_rows(lo: int, hi: int, issuer: np.ndarray, exp_hour: int) -> bytes:
    """Fingerprints of rows ``[lo, hi)``: 16 bytes each."""
    head = [int(exp_hour).to_bytes(4, "big", signed=True)
            + k.to_bytes(4, "big") + bytes([16, SERIAL_LEAD])
            + (SERIAL_BASE >> 64).to_bytes(7, "big")
            for k in range(int(issuer.max()) + 1)]
    low = np.arange(lo, hi, dtype=">u8").tobytes()  # the serials' last 8 bytes
    sha = hashlib.sha256
    return b"".join(
        sha(head[k] + low[at:at + 8]).digest()[16:]
        for at, k in zip(range(0, len(low), 8), issuer.tolist()))


def _hash_chunk(args) -> tuple[int, bytes]:
    standing, exp_hour, lo, hi = args
    return lo, hash_rows(lo, hi, standing.issuer_of(np.arange(lo, hi)),
                         exp_hour)


def fingerprints(standing: Standing, exp_hour: int,
                 workers: int) -> np.ndarray:
    """``uint32[rows, 4]``; the hashing spread over ``workers`` processes
    (forked before JAX is loaded: they hold nothing of it)."""
    rows = standing.rows
    keys = np.empty((rows, 4), np.uint32)
    chunks = [(standing, exp_hour, lo, min(rows, lo + CHUNK))
              for lo in range(0, rows, CHUNK)]

    def take(lo: int, raw: bytes) -> None:
        got = np.frombuffer(raw, dtype=">u4").reshape(-1, 4)
        keys[lo:lo + got.shape[0]] = got

    if workers <= 1 or len(chunks) == 1:
        for c in chunks:
            take(*_hash_chunk(c))
    else:
        with ProcessPoolExecutor(
                workers, mp_context=multiprocessing.get_context("fork")) as pool:
            for lo, raw in pool.map(_hash_chunk, chunks):
                take(lo, raw)
    # The all-zero fingerprint marks an empty slot (``_desentinel``).
    keys[~keys.any(axis=1), 3] = 1
    return keys


def place(keys: np.ndarray, buckets: int) -> np.ndarray:
    """The bucket each row lives in: its home, or where a row past its
    home's 24 slots comes to rest, bucket by bucket. Which 24 stay is
    of no matter to a reader: it walks on only past full buckets."""
    mask = buckets - 1
    dest = ((keys[:, 0] ^ (keys[:, 1] * np.uint32(0x9E3779B9)))
            & np.uint32(mask)).astype(np.int64)
    fill = np.bincount(dest, minlength=buckets)
    crowded = fill > SLOTS
    # The rows of uncrowded homes stay; those of crowded ones are dealt
    # out below, 24 to their home and the others onward.
    moving = np.flatnonzero(crowded[dest])
    fill[crowded] = 0
    while moving.size:
        moving = moving[np.argsort(dest[moving], kind="stable")]
        at = dest[moving]
        rank = np.arange(moving.size) - np.searchsorted(at, at, side="left")
        stays = rank < SLOTS - fill[at]
        fill += np.bincount(at[stays], minlength=buckets)
        moving = moving[~stays]
        dest[moving] = (dest[moving] + 1) & mask
    return dest


def members(standing: Standing, issuer_ids: list[str], exp_hour: int,
            workers: int = 1, leave_out: int | None = None) -> dict:
    """The base's members. ``leave_out`` names one row that is left out
    of all of them (the control's way in: ``tests/breaks.py``)."""
    buckets = table_slots(standing.slots_log2) // SLOTS
    rows = standing.rows
    if rows > buckets * SLOTS or standing.issuers > MAX_ISSUERS:
        raise ValueError("more standing rows than slots, or issuers than "
                         "the meta word holds")
    keys = fingerprints(standing, exp_hour, workers)
    row = np.arange(rows)
    issuer = standing.issuer_of(row)
    if leave_out is not None:
        keys, issuer = keys[row != leave_out], issuer[row != leave_out]
    dest = place(keys, buckets)
    order = np.argsort(dest, kind="stable")
    meta = (issuer.astype(np.uint32) << np.uint32(META_HOUR_BITS)) | np.uint32(
        exp_hour - BASE_HOUR)
    totals = np.zeros((MAX_ISSUERS,), np.int64)
    totals[: standing.issuers] = np.bincount(issuer,
                                             minlength=standing.issuers)
    nothing = np.zeros((MAX_ISSUERS,), np.int64)
    return {
        "fill": np.bincount(dest, minlength=buckets).astype(np.uint8),
        "keys": keys[order], "meta": meta[order],
        "count": np.array(keys.shape[0], np.int32),
        "layout": np.array("bucket"), "n_shards": np.int64(1),
        "base_hour": np.int64(BASE_HOUR),
        "registry": np.frombuffer(json.dumps(
            issuer_ids[: standing.issuers]).encode(), dtype=np.uint8),
        "issuer_totals": totals,
        "verify_verified": nothing, "verify_failed": nothing,
        "host_keys": np.zeros((0, 2), np.int64),
        "host_vals": np.array([], dtype=object),
        "crl_sets": np.frombuffer(b"{}", dtype=np.uint8),
        "dn_sets": np.frombuffer(b"{}", dtype=np.uint8),
    }


STORED = ("fill", "keys", "meta")


def write_base(path: str, table: dict) -> None:
    """The ``.npz`` and its manifest at ``path``, each landed by rename:
    the base first, as the program lands them."""
    tmp = path + ".tmp"
    with open(tmp, "wb") as fh:
        with zipfile.ZipFile(fh, mode="w", compression=zipfile.ZIP_DEFLATED,
                             allowZip64=True) as zf:
            for name in sorted(table):
                info = zipfile.ZipInfo(name + ".npy")
                info.compress_type = (zipfile.ZIP_STORED if name in STORED
                                      else zipfile.ZIP_DEFLATED)
                with zf.open(info, "w", force_zip64=True) as member:
                    np.lib.format.write_array(
                        member, np.asanyarray(table[name]), allow_pickle=True)
        fh.flush()
        os.fsync(fh.fileno())
    sha = hashlib.sha256()
    with open(tmp, "rb") as fh:
        for block in iter(lambda: fh.read(1 << 22), b""):
            sha.update(block)
    os.replace(tmp, path)
    manifest = {"baseSha256": sha.hexdigest(), "chain": [],
                "format": "CTMRCK02", "maxChain": 8}
    with open(path + MANIFEST_SUFFIX + ".tmp", "wb") as fh:
        fh.write(json.dumps(manifest, sort_keys=True,
                            separators=(",", ":")).encode() + b"\n")
        fh.flush()
        os.fsync(fh.fileno())
    os.replace(path + MANIFEST_SUFFIX + ".tmp", path + MANIFEST_SUFFIX)


def ensure(cache_dir: str, standing: Standing, issuer_ids: list[str],
           exp_hour: int, workers: int = 1) -> tuple[str, float]:
    """The cached base's path, built now if this checkout has none, and
    the seconds the build took (0.0 for a file that was there)."""
    path = os.path.join(cache_dir, cache_name(standing, issuer_ids, exp_hour))
    if os.path.exists(path) and os.path.exists(path + MANIFEST_SUFFIX):
        return path, 0.0
    t0 = time.monotonic()
    os.makedirs(cache_dir, exist_ok=True)
    write_base(path, members(standing, issuer_ids, exp_hour, workers))
    return path, time.monotonic() - t0


def link_or_copy(src: str, dst: str) -> None:
    try:
        os.link(src, dst)
    except OSError:  # a file system without hard links
        shutil.copy2(src, dst)


def put(path: str, state_path: str) -> None:
    """The cached base where ``aggStatePath`` points, as a link: the
    program lands every file by rename, so it never writes to these."""
    for suffix in ("", MANIFEST_SUFFIX):
        link_or_copy(path + suffix, state_path + suffix)
