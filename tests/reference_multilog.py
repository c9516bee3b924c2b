"""The plain reference of a multi-log ingest: a dict of sets and a
cursor a log, fed entry by entry from get-entries JSON. No batching, no
device, nothing of the program: ``json``, ``base64`` and the offsets of
the committed templates (``benchmark/fixtures/templates.json``), whose
leaves carry their serial at a known place.

    ref = Reference()
    ref.feed("log0", 0, body)       # one get-entries response from index 0
    ref.counts()                    # {(issuer_id, exp_date_id): serials}
    ref.cursors                     # {"log0": entries fed}

The same semantics as the program's: a serial counts once per (issuer,
expiry date) however many logs or entries carry it, and a log's cursor
stands behind the last entry fed.
"""

from __future__ import annotations

import base64
import json
import os

TEMPLATES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "fixtures", "templates.json")


class Reference:
    def __init__(self, templates: str = TEMPLATES):
        with open(templates) as fh:
            doc = json.load(fh)
        self.serial_len = int(doc["serial_len"])
        self.exp_date_id = doc["exp_date_id"]
        # CA certificate -> (issuer id, leaf length -> serial offset)
        self.by_ca: dict[bytes, tuple[str, dict[int, int]]] = {}
        for issuer in doc["issuers"]:
            offsets = {int(leaf["der_len"]): int(leaf["serial_off"])
                       for leaf in issuer["leaves"].values()}
            self.by_ca[base64.b64decode(issuer["issuer_der"])] = (
                issuer["issuer_id"], offsets)
        self.serials: dict[tuple[str, str], set[bytes]] = {}
        self.cursors: dict[str, int] = {}
        self.entries = 0

    def feed(self, log: str, start: int, body: bytes) -> int:
        """One get-entries response of ``log`` that begins at ``start``;
        returns how many entries it held."""
        entries = json.loads(body)["entries"]
        for entry in entries:
            leaf = base64.b64decode(entry["leaf_input"])
            # MerkleTreeLeaf: version, leaf type, timestamp (8), entry
            # type (2), then the certificate behind a 24-bit length.
            n = int.from_bytes(leaf[12:15], "big")
            der = leaf[15:15 + n]
            # extra_data: the chain behind a 24-bit length, its first
            # certificate (the CA) behind another.
            extra = base64.b64decode(entry["extra_data"])
            ca = extra[6:6 + int.from_bytes(extra[3:6], "big")]
            issuer_id, offsets = self.by_ca[ca]
            off = offsets[len(der)]
            self.serials.setdefault(
                (issuer_id, self.exp_date_id), set()).add(
                der[off:off + self.serial_len])
        self.entries += len(entries)
        self.cursors[log] = start + len(entries)
        return len(entries)

    def counts(self) -> dict[tuple[str, str], int]:
        return {key: len(found) for key, found in self.serials.items()}

    def unique(self) -> int:
        return sum(len(found) for found in self.serials.values())
