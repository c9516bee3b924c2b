"""Pallas SHA-256 kernel: bit-equality with the XLA path and hashlib.

Runs in interpret mode on the CPU test platform; the real-TPU tier
(CT_TPU_TESTS=1) compiles the actual Mosaic kernel.
"""

import hashlib
import os

import numpy as np
import pytest

from ct_mapreduce_tpu.ops import pallas_sha256, sha256

from tests.conftest import on_tpu


def _blocks(n: int, seed: int = 7) -> tuple[np.ndarray, list[bytes]]:
    """n random ≤55-byte messages, FIPS-padded into single blocks."""
    rng = np.random.default_rng(seed)
    blocks = np.zeros((n, 16), np.uint32)
    msgs = []
    for i in range(n):
        msg = rng.integers(0, 256, rng.integers(0, 56), dtype=np.uint8).tobytes()
        msgs.append(msg)
        blocks[i] = sha256.pad_message_np(msg, total_blocks=1)[0]
    return blocks, msgs


def test_pallas_matches_xla_and_hashlib():
    interpret = not on_tpu()
    blocks, msgs = _blocks(256)
    got = np.asarray(
        pallas_sha256.sha256_single_block_pallas(blocks, interpret=interpret)
    )
    ref = np.asarray(sha256.sha256_single_block(blocks))
    np.testing.assert_array_equal(got, ref)
    for i, msg in enumerate(msgs):
        assert sha256.digest_np(got[i]) == hashlib.sha256(msg).digest()


def test_pallas_fingerprint_tail_words():
    interpret = not on_tpu()
    blocks, _ = _blocks(128)
    fp = np.asarray(
        pallas_sha256.sha256_fingerprint64_pallas(blocks, interpret=interpret)
    )
    full = np.asarray(sha256.sha256_single_block(blocks))
    np.testing.assert_array_equal(fp, full[:, 4:])


def test_pallas_grid_tiling():
    """Batch larger than one lane tile exercises the grid."""
    interpret = not on_tpu()
    blocks, _ = _blocks(pallas_sha256.LANE_TILE * 2)
    got = np.asarray(
        pallas_sha256.sha256_single_block_pallas(blocks, interpret=interpret)
    )
    ref = np.asarray(sha256.sha256_single_block(blocks))
    np.testing.assert_array_equal(got, ref)


def test_dispatcher_stays_on_xla_off_tpu(monkeypatch):
    monkeypatch.setenv("CTMR_PALLAS", "1")
    blocks, _ = _blocks(64)
    # CPU backend → dispatcher must fall back to the XLA path (no error).
    out = np.asarray(sha256.sha256_fingerprint64(blocks))
    assert out.shape == (64, 4)


def test_dispatcher_picks_pallas_on_tpu(monkeypatch):
    """The gate's other side, which only the chip takes: on a backend
    named ``tpu`` the ingest widths select the Pallas kernel, and a
    backend that cannot initialise is an error, not the XLA scan."""
    import jax

    monkeypatch.setenv("CTMR_PALLAS", "1")
    monkeypatch.delenv("CTMR_SHA_TILE", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sha256._pallas_enabled(65536)
    assert sha256._pallas_enabled(16384)
    assert not sha256._pallas_enabled(640)  # shape rule: tile must divide
    picked = []
    monkeypatch.setattr(
        pallas_sha256, "sha256_fingerprint64_pallas",
        lambda block: picked.append(block.shape) or block[..., :4])
    sha256.sha256_fingerprint64(np.zeros((16384, 16), np.uint32))
    assert picked == [(16384, 16)]
    monkeypatch.setenv("CTMR_PALLAS", "0")  # the explicit opt-out stays
    assert not sha256._pallas_enabled(65536)
    monkeypatch.setenv("CTMR_PALLAS", "1")

    def down():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "default_backend", down)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        sha256._pallas_enabled(65536)
