"""Host-side ingest: CT log HTTP client, leaf decode, sync engine.

The reference's map side — download goroutines + parse/store worker
pool (/root/reference/cmd/ct-fetch/ct-fetch.go) — rebuilt as the host
pipeline that feeds packed entry batches to the device ops. Stage
layout mirrors §3.1-3.3 of SURVEY.md:

  ctclient    CT log v1 HTTP API (get-sth, get-entries×1000, 429 backoff)
  leaf        RFC 6962 TLS-struct decode (MerkleTreeLeaf, chains)
  sync        LogSyncEngine / LogWorker: download → queue → store workers
  fleet       multi-worker partitioned feed + leader-coordinated lifecycle
  health      /health endpoint (503 before first update, 500 stalled)
"""

from ct_mapreduce_tpu.ingest.ctclient import CTLogClient, SignedTreeHead, short_url
from ct_mapreduce_tpu.ingest.fleet import (
    FleetService,
    partition_logs,
    partition_map,
    partition_range,
)
from ct_mapreduce_tpu.ingest.leaf import DecodedEntry, decode_entry
from ct_mapreduce_tpu.ingest.sync import LogSyncEngine, LogWorker

__all__ = [
    "CTLogClient",
    "SignedTreeHead",
    "short_url",
    "DecodedEntry",
    "decode_entry",
    "FleetService",
    "LogSyncEngine",
    "LogWorker",
    "partition_logs",
    "partition_map",
    "partition_range",
]
