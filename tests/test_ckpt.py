"""CTMRCK02 incremental checkpoints (round 22, ISSUE 18).

The contract under test: a versioned chain — one full **base**
snapshot plus append-only **delta segments** carrying only each epoch
tick's churn — restores STATE-IDENTICAL to the ck01 full-save oracle
(tests/ckptstate.py::ckpt_state_digest), stays bounded by ``ckptMaxChain``
via compaction anchors, survives tampering/truncation with loud
``CkptError``s (a listed-but-broken chain must never half-load), heals
the one legal stale artifact (a manifest older than its base), and a
SIGKILL at any write boundary leaves a validating, resumable chain
(the fleet-level version of that last clause lives in
tests/test_multiprocess.py; the pre-rename boundaries are covered here
with a self-killing child process).
"""

import os
import signal
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax

if os.environ.get("CT_TPU_TESTS", "") == "":
    jax.config.update("jax_platforms", "cpu")

from ct_mapreduce_tpu.agg import ckpt
from ct_mapreduce_tpu.agg.aggregator import HostSnapshotAggregator
from tests import ckptstate as harness

ENTRIES = 400
BITS = 12


def _mk(tmp_path, mode="ck02", max_chain=0, entries=ENTRIES):
    agg, eh = harness.build_aggregator(entries, BITS)
    agg.configure_checkpointing(mode=mode, max_chain=max_chain)
    path = str(tmp_path / "agg.npz")
    return agg, eh, path


def _reader(path, capacity=1 << BITS):
    r = HostSnapshotAggregator(capacity=capacity)
    r.load_checkpoint(path)
    return r


# -- segment codec -------------------------------------------------------


def test_segment_codec_roundtrip():
    dev = [(0, 401000, b"\x00" * 8 + b"\x01" * 8), (2, 401007, b"ab")]
    host = [(1, 401001, b"longserial" * 4)]
    blob = {"baseHour": 400000, "countAfter": 7}
    data, header = ckpt.encode_segment(3, "f" * 64, dev, host, blob)
    assert header["seq"] == 3
    assert header["targetSha256"] == ckpt.chain_token(
        "f" * 64, header["payloadSha256"])
    h2, d2, hs2, b2 = ckpt.decode_segment(data)
    assert h2 == header
    assert d2 == dev
    assert hs2 == host
    assert b2 == blob


def test_segment_codec_rejects_corruption():
    data, _ = ckpt.encode_segment(
        1, "0" * 64, [(0, 401000, b"serialserial")], [], {"x": 1})
    # Any flipped payload byte breaks payloadSha256.
    bad = bytearray(data)
    bad[-1] ^= 0xFF
    with pytest.raises(ckpt.CkptError):
        ckpt.decode_segment(bytes(bad))
    # Truncation anywhere breaks the self-delimiting size check.
    for cut in (4, len(data) // 2, len(data) - 1):
        with pytest.raises(ckpt.CkptError):
            ckpt.decode_segment(data[:cut])
    with pytest.raises(ckpt.CkptError):
        ckpt.decode_segment(b"NOTCK02!" + data[8:])


# -- chain round trip vs the ck01 oracle ---------------------------------


def test_chain_restore_matches_ck01_oracle(tmp_path):
    agg, eh, path = _mk(tmp_path)
    agg.save_checkpoint(path)          # base
    harness.ckpt_churn(agg, eh, 37, ENTRIES)
    agg.save_checkpoint(path)          # segment 1
    harness.ckpt_churn(agg, eh, 23, ENTRIES + 1000)
    agg.save_checkpoint(path)          # segment 2
    want = harness.ckpt_state_digest(agg)

    chain = ckpt.resolve_chain(path)
    assert len(chain.segments) == 2
    assert chain.segments[0][0]["devRows"] == 37
    assert chain.segments[1][0]["devRows"] == 23

    # The ck01 oracle: a full save of the same state.
    oracle = str(tmp_path / "oracle.npz")
    agg.configure_checkpointing(mode="ck01")
    agg.save_checkpoint(oracle)
    assert not os.path.exists(ckpt.manifest_path(oracle))

    for src in (path, oracle):
        assert harness.ckpt_state_digest(_reader(src)) == want


@pytest.mark.slow
def test_restored_writer_extends_chain(tmp_path):
    """A restored aggregator continues the chain: its next save
    extends rather than re-anchoring — including after a restart from
    a plain base with no manifest (the synthesized-manifest path)."""
    from ct_mapreduce_tpu.agg.aggregator import TpuAggregator

    agg, eh, path = _mk(tmp_path)
    agg.save_checkpoint(path)
    harness.ckpt_churn(agg, eh, 11, ENTRIES)
    agg.save_checkpoint(path)

    r = TpuAggregator(capacity=1 << BITS, grow_at=0.0)
    r.load_checkpoint(path)
    r.configure_checkpointing(mode="ck02")
    harness.ckpt_churn(r, eh, 13, ENTRIES + 2000)
    r.save_checkpoint(path)
    chain = ckpt.resolve_chain(path)
    assert [s[0]["seq"] for s in chain.segments] == [1, 2]
    assert harness.ckpt_state_digest(_reader(path)) == \
        harness.ckpt_state_digest(r)


def test_issuer_registered_during_a_segment_save_is_not_lost(tmp_path):
    """The store thread's decode registers issuers under none of the
    save's locks. One that lands between the save's two reads of the
    registry (the shadow and the segment's adds) must reach the chain
    in this segment or the next: rows that name it are then already
    durable, and a later tick with nothing else dirty is a no-op."""
    from ct_mapreduce_tpu.core.types import Issuer

    agg, eh, path = _mk(tmp_path)
    agg.save_checkpoint(path)
    harness.ckpt_churn(agg, eh, 11, ENTRIES)
    late = Issuer.from_string("bGF0ZS1pc3N1ZXItcmVnaXN0ZXJlZC1taWQtc2F2ZQ==")

    def lands_after(read):
        def reads_then_registers(*args):
            out = read(*args)
            agg.registry.assign_issuer(late)
            return out
        return reads_then_registers

    # Whichever read of the registry the save makes first, the issuer
    # is registered right after it and before the other.
    agg._ckpt_take_shadow = lands_after(agg._ckpt_take_shadow)
    agg.registry.ids_from = lands_after(agg.registry.ids_from)
    agg.save_checkpoint(path)
    agg.save_checkpoint(path)
    assert agg.registry.index_of_issuer_id(late.id()) is not None
    assert harness.ckpt_state_digest(_reader(path)) == \
        harness.ckpt_state_digest(agg)
    assert _reader(path).registry.index_of_issuer_id(late.id()) == \
        agg.registry.index_of_issuer_id(late.id())


def test_empty_tick_writes_no_segment(tmp_path):
    agg, eh, path = _mk(tmp_path)
    agg.save_checkpoint(path)
    agg.save_checkpoint(path)          # nothing folded since the base
    assert len(ckpt.resolve_chain(path).segments) == 0
    assert not os.path.exists(ckpt.segment_path(path, 1))


# -- compaction / bounded chains -----------------------------------------


def test_compaction_bounds_chain(tmp_path):
    agg, eh, path = _mk(tmp_path, max_chain=2)
    agg.save_checkpoint(path)
    lengths = []
    for t in range(5):
        harness.ckpt_churn(agg, eh, 5, ENTRIES + 100 * t)
        agg.save_checkpoint(path)
        n = len(ckpt.resolve_chain(path).segments)
        lengths.append(n)
        assert n <= 2
    # Ticks 1,2 extend; tick 3 anchors (chain at maxChain); 4,5 extend.
    assert lengths == [1, 2, 0, 1, 2]
    # The anchor really cleaned the superseded segments up (seq 1-2 of
    # the OLD chain are gone; the new chain reuses those seq numbers).
    assert harness.ckpt_state_digest(_reader(path)) == \
        harness.ckpt_state_digest(agg)


# -- tampering / healing -------------------------------------------------


@pytest.mark.slow
def test_stale_manifest_heals_to_base_alone(tmp_path):
    """Crash ordering rule: a compaction renames its fresh base BEFORE
    its fresh manifest, so a manifest whose baseSha256 doesn't match
    the on-disk base is by construction OLDER than the base — the base
    alone is the newest durable full state and must win."""
    agg, eh, path = _mk(tmp_path)
    agg.save_checkpoint(path)
    want_base = harness.ckpt_state_digest(agg)
    harness.ckpt_churn(agg, eh, 9, ENTRIES)
    agg.save_checkpoint(path)

    # Simulate the mid-compaction crash: the base changes under the
    # manifest (zip archives tolerate trailing bytes, so the npz still
    # loads — but its sha no longer matches the manifest). The healed
    # restore is the BASE's state: in a real crash the fresh anchor is
    # itself a complete snapshot, so dropping the stale chain is
    # exactly right — never replay old segments onto a newer base.
    with open(path, "ab") as fh:
        fh.write(b"\x00")
    chain = ckpt.resolve_chain(path)
    assert len(chain.segments) == 0
    assert harness.ckpt_state_digest(_reader(path)) == want_base


def test_broken_listed_chain_raises(tmp_path):
    agg, eh, path = _mk(tmp_path)
    agg.save_checkpoint(path)
    harness.ckpt_churn(agg, eh, 9, ENTRIES)
    agg.save_checkpoint(path)

    seg = ckpt.segment_path(path, 1)
    raw = open(seg, "rb").read()
    # Corrupt one payload byte: the LISTED segment no longer verifies.
    bad = bytearray(raw)
    bad[-1] ^= 0xFF
    open(seg, "wb").write(bytes(bad))
    with pytest.raises(ckpt.CkptError):
        ckpt.resolve_chain(path)
    # A listed-but-missing segment is just as fatal: never half-load.
    os.unlink(seg)
    with pytest.raises(ckpt.CkptError):
        ckpt.resolve_chain(path)
    open(seg, "wb").write(raw)
    assert len(ckpt.resolve_chain(path).segments) == 1


@pytest.mark.slow
def test_disk_tip_mismatch_forces_anchor(tmp_path):
    """If the on-disk manifest no longer matches the writer's in-memory
    tip (another process extended it, an operator rolled files back),
    extending would fork the chain — the writer must anchor instead."""
    agg, eh, path = _mk(tmp_path)
    agg.save_checkpoint(path)
    harness.ckpt_churn(agg, eh, 9, ENTRIES)
    agg.save_checkpoint(path)

    man = ckpt.read_manifest(path)
    man["chain"] = []                  # roll the manifest back
    ckpt.write_manifest(path, man)
    harness.ckpt_churn(agg, eh, 9, ENTRIES + 500)
    agg.save_checkpoint(path)          # must anchor, not extend
    chain = ckpt.resolve_chain(path)
    assert len(chain.segments) == 0
    assert harness.ckpt_state_digest(_reader(path)) == \
        harness.ckpt_state_digest(agg)


# -- poisons: the dirty log drops, the next save anchors ------------------


@pytest.mark.slow
def test_serialless_fold_poisons_log(tmp_path):
    agg, eh, path = _mk(tmp_path)
    agg.save_checkpoint(path)
    harness.ckpt_churn(agg, eh, 9, ENTRIES)
    agg.want_serials = False           # count-only fold: rows untracked
    harness.ckpt_churn(agg, eh, 9, ENTRIES + 500)
    agg.want_serials = True
    assert agg._ckpt_dirty_lost
    agg.save_checkpoint(path)
    assert len(ckpt.resolve_chain(path).segments) == 0  # anchored
    assert harness.ckpt_state_digest(_reader(path)) == \
        harness.ckpt_state_digest(agg)


def test_segment_budget_poisons_log(tmp_path):
    agg, eh, path = _mk(tmp_path)
    agg.save_checkpoint(path)
    # Park the accounting just under the budget; the next recorded row
    # must tip it over and poison (no need to fold 256 MB of churn).
    budget = agg._ckpt_resolved().segment_budget_mb << 20
    agg._ckpt_row_bytes = budget - 1
    harness.ckpt_churn(agg, eh, 9, ENTRIES)
    assert agg._ckpt_dirty_lost
    agg.save_checkpoint(path)
    assert len(ckpt.resolve_chain(path).segments) == 0
    assert harness.ckpt_state_digest(_reader(path)) == \
        harness.ckpt_state_digest(agg)


@pytest.mark.slow
def test_grow_poisons_log(tmp_path):
    agg, eh, path = _mk(tmp_path)
    agg.save_checkpoint(path)
    harness.ckpt_churn(agg, eh, 9, ENTRIES)
    agg.grow(1 << (BITS + 1))          # rebuilt table: row log is moot
    assert agg._ckpt_dirty_lost
    agg.save_checkpoint(path)
    assert len(ckpt.resolve_chain(path).segments) == 0
    assert harness.ckpt_state_digest(_reader(path)) == \
        harness.ckpt_state_digest(agg)


# -- filter capture rides the chain --------------------------------------


@pytest.mark.slow
def test_capture_tokens_survive_chain_restore(tmp_path):
    agg, eh, path = _mk(tmp_path)
    agg.enable_filter_capture()
    harness.ckpt_churn(agg, eh, 17, ENTRIES)
    agg.save_checkpoint(path)          # base (capture reconfig anchors)
    harness.ckpt_churn(agg, eh, 19, ENTRIES + 1000)
    agg.save_checkpoint(path)
    assert len(ckpt.resolve_chain(path).segments) == 1

    r = HostSnapshotAggregator(capacity=1 << BITS)
    r.enable_filter_capture()
    r.load_checkpoint(path)
    assert r.capture_content_hashes() == agg.capture_content_hashes()
    assert harness.ckpt_state_digest(r) == harness.ckpt_state_digest(agg)


# -- merge plane over chains ---------------------------------------------


@pytest.mark.slow
def test_merge_loads_chains(tmp_path):
    from ct_mapreduce_tpu.agg import merge
    from ct_mapreduce_tpu.core.types import Issuer

    agg, eh, path = _mk(tmp_path)
    # drain() maps issuer idx 0 through the registry (the synthetic
    # harness corpus folds everything under one issuer).
    agg.registry.assign_issuer(Issuer.from_string("CN=Test CA"))
    harness.ckpt_churn(agg, eh, 15, ENTRIES)
    agg.save_checkpoint(path)
    harness.ckpt_churn(agg, eh, 15, ENTRIES + 1000)
    agg.save_checkpoint(path)
    assert len(ckpt.resolve_chain(path).segments) >= 1
    snap = merge.load_checkpoints([path]).drain()
    assert snap.total == int(agg._table_fill)


# -- pre-rename kill boundaries (self-killing child) ----------------------

_KILL_CHILD = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("CT_TPU_TESTS", None)
    sys.path.insert(0, sys.argv[1])
    path, point = sys.argv[2], sys.argv[3]

    from tests import ckptstate as harness

    agg, eh = harness.build_aggregator(400, 12)
    agg.configure_checkpointing(mode="ck02")
    agg.save_checkpoint(path)                       # durable base
    print("DIGEST " + harness.ckpt_state_digest(agg), flush=True)
    harness.ckpt_churn(agg, eh, 21, 400)
    os.environ["CTMR_CKPT_KILL"] = point
    agg.save_checkpoint(path)                       # dies inside
    raise SystemExit(3)                             # must not be reached
""")


@pytest.mark.slow
@pytest.mark.parametrize("point", ["seg-pre-rename", "manifest-pre-rename"])
@pytest.mark.timeout(180)
def test_kill_before_rename_keeps_last_tick(tmp_path, point):
    """Dying BEFORE a rename publishes nothing: the durable chain is
    exactly the previous tick's (here: the base), and it restores
    byte-for-byte to the digest the child printed at that tick.
    (The post-rename boundaries run under the full fleet worker in
    tests/test_multiprocess.py::test_fleet_kill_points_ck02.)"""
    repo = str(Path(__file__).resolve().parent.parent)
    path = str(tmp_path / "agg.npz")
    child = tmp_path / "kill_child.py"
    child.write_text(_KILL_CHILD)
    env = dict(os.environ)
    env.pop("CTMR_CKPT_KILL", None)
    proc = subprocess.run(
        [sys.executable, str(child), repo, path, point],
        capture_output=True, text=True, timeout=150, env=env)
    assert proc.returncode == -signal.SIGKILL, proc.stdout + proc.stderr
    digest = next(line.split(" ", 1)[1]
                  for line in proc.stdout.splitlines()
                  if line.startswith("DIGEST "))

    chain = ckpt.resolve_chain(path)
    assert len(chain.segments) == 0
    if point == "manifest-pre-rename":
        # The segment's rename already happened; it's just unlisted.
        assert os.path.exists(ckpt.segment_path(path, 1))
    assert harness.ckpt_state_digest(_reader(path)) == digest
