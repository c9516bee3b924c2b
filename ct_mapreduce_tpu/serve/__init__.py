"""Query plane: online membership serving over the live dedup table.

The map side of the system (ingest → dedup → counts) answers "has this
certificate been seen?" only in batch — ``storage-statistics`` drains a
snapshot and prints text. This package is the read/serve side: a
batched membership oracle ("is serial S known for (issuer, expDate)?")
plus per-issuer metadata lookups, served at high QPS against the LIVE
aggregator state while ingest keeps running.

Three pieces (ISSUE 5):

- :mod:`~ct_mapreduce_tpu.serve.snapshot` — epoch-pinned, immutable
  read views captured under the aggregator's fold/table locks, so a
  mid-grow or mid-insert step never tears a read; staleness is bounded
  and surfaced per response.
- :mod:`~ct_mapreduce_tpu.serve.batcher` — deadline-driven dynamic
  micro-batching (the inference-serving discipline): concurrent
  requests coalesce into one padded pow2-width ``contains`` batch,
  with max-batch / max-delay knobs, per-request deadlines, and a
  bounded admission queue that sheds with explicit ``overloaded``
  rejections instead of queueing without bound.
- :mod:`~ct_mapreduce_tpu.serve.server` — the stdlib HTTP JSON API
  (``queryPort`` directive; ``/query``, ``/issuer/<id>``,
  ``/healthz``, ``/getcert``) and the
  :class:`~ct_mapreduce_tpu.serve.server.MembershipOracle` that ties
  the two together. :mod:`~ct_mapreduce_tpu.serve.client` is the
  matching client (the ``ct-query`` binary).
"""

from ct_mapreduce_tpu.serve.batcher import (  # noqa: F401
    DeadlineExceeded,
    MicroBatcher,
    Overloaded,
)
from ct_mapreduce_tpu.serve.cache import HotSerialCache  # noqa: F401
from ct_mapreduce_tpu.serve.snapshot import (  # noqa: F401
    ReplicaPool,
    TableView,
)
