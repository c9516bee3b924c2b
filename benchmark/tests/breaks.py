"""Ways to break a guarantee the configurations state, underneath the
timed path, so that ``correct`` can be seen to come out false. Each
patches the program in this process; none changes a batch's shape, so
the broken run compiles nothing the sound run does not.
"""

from __future__ import annotations

import os


def lost_entry() -> None:
    """Counts exact -> one entry lost: on three pages the store path
    sees the page's first entry again in place of its last; where the
    table has standing rows, in place of the last that repeats none of
    them (a known certificate lost changes no count, and should not)."""
    import base64

    import prefill
    from ct_mapreduce_tpu.ingest import sync

    real = sync.AggregatorSink.store_raw_batch
    seen = {"pages": 0}
    # A standing row's serial as a leaf carries it: the templates' first
    # byte and SERIAL_BASE's seven; no log's serial has the 0x10.
    standing = bytes([prefill.SERIAL_LEAD]) + (
        prefill.SERIAL_BASE >> 64).to_bytes(7, "big")

    def store_raw_batch(self, raw):
        seen["pages"] += 1
        if seen["pages"] in (700, 900, 1100) or (
                seen["pages"] in (20, 21, 22) and len(raw) < 512):
            lost = next((i for i in range(len(raw) - 1, 0, -1) if standing
                         not in base64.b64decode(raw.leaf_inputs[i])), -1)
            raw.leaf_inputs[lost] = raw.leaf_inputs[0]
            raw.extra_datas[lost] = raw.extra_datas[0]
        return real(self, raw)

    sync.AggregatorSink.store_raw_batch = store_raw_batch


def deferred_checkpoint() -> None:
    """Durability -> the program says it is idle before the round's
    checkpoint is on disk: the two saves of the round after the warm-up
    round (the cursor hook's and the round's end) return at once, as
    those of a program that defers or drops its save would. Whatever a
    later round or the exit writes, the file as it stands when the
    program says ``idle`` is the warm-up round's. For cells of one
    log."""
    from ct_mapreduce_tpu.models import ingest_model

    real = ingest_model.IngestModel.save
    seen = {"saves": 0}

    def save(self):
        seen["saves"] += 1
        if seen["saves"] in (3, 4):  # after the warm-up round's hook and end
            return None
        return real(self)

    ingest_model.IngestModel.save = save


def wrong_answer() -> None:
    """Answers exact -> every seventh membership answer of the query
    plane says the opposite, as one from a stale or approximate tier
    would: a serial that was fed long ago comes back unknown, one never
    fed comes back known. For cells with a query generator."""
    from ct_mapreduce_tpu.serve import server

    real = server.MembershipOracle.query_raw
    seen = {"answers": 0}

    def query_raw(self, items, timeout_s=None):
        out = []
        for known, epoch, staleness in real(self, items, timeout_s=timeout_s):
            seen["answers"] += 1
            out.append((known ^ (seen["answers"] % 7 == 0), epoch, staleness))
        return out

    server.MembershipOracle.query_raw = query_raw


def lost_standing_row() -> None:
    """Resume -> one row of the checkpoint the tailer started from is in
    no later one: the base is written with one standing row left out,
    one the run's stream does not repeat (the lowest such), so nothing
    brings it back. For cells with a ``table_prefill`` block; the base
    is built anew, beside the cache and not into it."""
    import fixture as fx
    import harness
    import prefill

    def place_base(spec, seed, config, state_path, workers):
        standing = spec.standing
        tpl = fx.Templates()
        repeated = {int(j) for log in fx.RunFixture(spec, seed).logs
                    for j in log.standing_of[log.standing_of >= 0]}
        lost = next(j for j in range(standing.rows) if j not in repeated)
        prefill.write_base(state_path, prefill.members(
            standing, tpl.issuer_ids, prefill.exp_hour_of(tpl.not_after),
            workers, leave_out=lost))
        return 0.0

    harness.place_base = place_base


def restore_ignored() -> None:
    """Resume -> the program starts from an empty table: the base is gone
    from where ``aggStatePath`` points between the harness's placing it
    and the program's start, as for a tailer that does not look for its
    checkpoint. For cells with a ``table_prefill`` block."""
    import harness
    import prefill

    real = harness.place_base

    def place_base(spec, seed, config, state_path, workers):
        built_s = real(spec, seed, config, state_path, workers)
        for suffix in ("", prefill.MANIFEST_SUFFIX):
            os.unlink(state_path + suffix)
        return built_s

    harness.place_base = place_base


BREAKS = {"lost_entry": lost_entry, "deferred_checkpoint": deferred_checkpoint,
          "wrong_answer": wrong_answer, "lost_standing_row": lost_standing_row,
          "restore_ignored": restore_ignored}
