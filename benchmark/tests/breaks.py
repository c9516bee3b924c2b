"""Ways to break a guarantee the configurations state, underneath the
timed path, so that ``correct`` can be seen to come out false. Each
patches the program in this process; none changes a batch's shape, so
the broken run compiles nothing the sound run does not.
"""

from __future__ import annotations


def lost_entry() -> None:
    """Counts exact -> one entry lost: on three pages the store path
    sees the page's first entry again in place of its last."""
    from ct_mapreduce_tpu.ingest import sync

    real = sync.AggregatorSink.store_raw_batch
    seen = {"pages": 0}

    def store_raw_batch(self, raw):
        seen["pages"] += 1
        if seen["pages"] in (700, 900, 1100) or (
                seen["pages"] in (20, 21, 22) and len(raw) < 512):
            raw.leaf_inputs[-1] = raw.leaf_inputs[0]
            raw.extra_datas[-1] = raw.extra_datas[0]
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


BREAKS = {"lost_entry": lost_entry, "deferred_checkpoint": deferred_checkpoint,
          "wrong_answer": wrong_answer}
