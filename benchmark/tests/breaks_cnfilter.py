#!/usr/bin/env python3
"""The controls of the configuration ``icarus-cnfilter-1chip``'s
guarantees **filter** and **filter_count**, beside ``breaks.py`` (which
holds the older guarantees' and is run the same way): a run of the cell
with one thing broken underneath, whose last line must read
``"correct": false``.

  python3 benchmark/tests/breaks_cnfilter.py <break> --workload backfill-1log-cnfilter --seed <n> --seconds <s> --trace 0
  JAX_PLATFORMS=cpu python3 benchmark/tests/breaks_cnfilter.py rehearse backfill-1log-cnfilter <seed> [trace] [<break>]

The first is ``control.py``'s run on the chip at the cell's own size,
the second ``rehearse_cell.py``'s at a rehearsal's; both know this
file's breaks beside ``breaks.py``'s. ``lost_entry`` here takes the
place of ``breaks.py``'s: under the filter an entry lost of an issuer
the filter drops changes no count, and should not.
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

import breaks  # noqa: E402

CONFIG = os.path.join(os.path.dirname(HERE), "configs",
                      "icarus-cnfilter-1chip.json")
DIRECTIVE = "issuerCNFilter"


def filter_ignored() -> None:
    """Filter -> the directive never reaches the program: the ini the
    program reads is written without ``issuerCNFilter``, as for a
    ct-fetch that does not know the directive. Every entry passes: the
    report holds sixteen issuers and the process counts nothing
    dropped."""
    import harness

    real = harness.write_ini

    def write_ini(config, *args, **kwargs):
        directives = {k: v for k, v in config["directives"].items()
                      if k != DIRECTIVE}
        return real(dict(config, directives=directives), *args, **kwargs)

    harness.write_ini = write_ini


def drop_uncounted() -> None:
    """Filter_count -> the device path drops and does not say so: what
    the aggregator adds to ``ct-fetch.certIsFilteredOut.*`` is held
    back, which is the program as it was before ISSUE 52 (its drops
    reached no output). The report is right; the process states no
    count."""
    from ct_mapreduce_tpu.agg import aggregator

    real = aggregator.incr_counter

    def incr_counter(*parts, value=1.0):
        if parts[:2] != ("ct-fetch", "certIsFilteredOut"):
            real(*parts, value=value)

    aggregator.incr_counter = incr_counter


def lost_entry() -> None:
    """Counts exact -> one entry lost of an issuer that PASSES the
    filter: on three pages the store path sees the page's first entry
    again in place of the last whose issuer's name the directive
    permits. The report lacks a serial (or counts a repeat's less), and
    where the first entry is another issuer's the process counts one
    drop too many."""
    import base64

    from ct_mapreduce_tpu.ingest import sync

    with open(CONFIG) as fh:
        passing = [p.encode() for p in
                   json.load(fh)["directives"][DIRECTIVE].split(",")]
    real = sync.AggregatorSink.store_raw_batch
    seen = {"pages": 0}

    def store_raw_batch(self, raw):
        seen["pages"] += 1
        if seen["pages"] in (700, 900, 1100) or (
                seen["pages"] in (20, 21, 22) and len(raw) < 512):
            lost = next((i for i in range(len(raw) - 1, 0, -1) if any(
                p in base64.b64decode(raw.leaf_inputs[i]) for p in passing)),
                None)
            if lost is not None:
                raw.leaf_inputs[lost] = raw.leaf_inputs[0]
                raw.extra_datas[lost] = raw.extra_datas[0]
        return real(self, raw)

    sync.AggregatorSink.store_raw_batch = store_raw_batch


breaks.BREAKS.update(filter_ignored=filter_ignored,
                     drop_uncounted=drop_uncounted, lost_entry=lost_entry)


def main(argv: list[str]) -> int:
    if argv[0] == "rehearse":
        import rehearse_cell

        return rehearse_cell.main(argv[1:])
    import control

    return control.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
