"""Pallas TPU kernel: batched single-block SHA-256 fingerprinting.

The XLA path (:mod:`ct_mapreduce_tpu.ops.sha256`) compiles the 64
compression rounds as a ``lax.scan`` with a rolling schedule — correct
and fast, but every round round-trips its [8, B] state through the
fusion boundary HBM traffic XLA chooses. This kernel keeps the entire
state and message schedule resident in VMEM for a tile of lanes and
runs all 64 rounds register-resident on the VPU: one HBM read of the
message block, one HBM write of the digest, nothing in between.

Layout: lanes ride the last (128-wide) axis. The [B, 16] message block
arrives transposed as [16, B]; per grid step the kernel sees a
[16, TILE] slice, state is an [8, TILE] VMEM scratch, and the rolling
16-entry schedule mutates the input tile in place.

Selection: :func:`ct_mapreduce_tpu.ops.sha256.sha256_fingerprint64`
dispatches here when ``CTMR_PALLAS=1`` and the backend is a TPU;
``interpret=True`` covers CPU tests (tests/test_pallas.py asserts
bit-equality with the XLA path and hashlib).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ct_mapreduce_tpu.ops.sha256 import _H0, _K

# Lanes per grid step. The r03 hardware number (0.50 ms @ 16,384 lanes)
# sits ~30x above the VPU's theoretical throughput for 64 unrolled
# rounds, which smells like per-grid-step overhead — CTMR_SHA_TILE
# exists so that the tile curve can be measured on hardware
# (VMEM comfortably fits tiles up to ~16K: [16, T] block + [8, T] out
# + ~24 live [T] vectors ≈ 2.9 MB at T=8192).
LANE_TILE = 512  # shipped default: the r03-measured configuration


def lane_tile() -> int:
    """Effective lanes-per-grid-step: CTMR_SHA_TILE env override, else
    LANE_TILE (consumed by the sha256 dispatch gate too)."""
    import os

    raw = os.environ.get("CTMR_SHA_TILE", "")
    if not raw:
        return LANE_TILE
    try:
        tile = int(raw)
        if tile < 128 or tile % 128:
            raise ValueError
    except ValueError:
        import warnings

        warnings.warn(
            f"ignoring CTMR_SHA_TILE={raw!r} (want a multiple of 128); "
            f"using {LANE_TILE}", stacklevel=2)
        return LANE_TILE
    return tile


def _rotr(x, n: int):
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def _kernel(k_ref, h0_ref, block_ref, out_ref):
    """k_ref: uint32[64, 1] round constants; h0_ref: uint32[8, 1];
    block_ref: uint32[16, TILE]; out_ref: uint32[8, TILE].

    (Constants arrive as inputs — Pallas kernels cannot capture array
    constants from the enclosing trace.)

    The 64 rounds are UNROLLED in Python so every schedule access is a
    static index: Mosaic's TPU lowering has no dynamic_slice, which is
    what a fori_loop + dynamic_index_in_dim formulation requires (that
    variant lowers only in interpret mode — it is kept below as
    ``_kernel_looped`` because interpreting 64 unrolled rounds is
    orders of magnitude slower than interpreting one fori_loop). The
    rolling 16-entry schedule lives in a Python list of [TILE] vectors
    — all VMEM/VREG resident for the whole compression."""
    tile = block_ref.shape[1]
    w = [block_ref[i, :] for i in range(16)]
    a, b, c, d, e, f, g, h = (
        jnp.broadcast_to(h0_ref[i, :], (tile,)) for i in range(8)
    )
    for t in range(64):
        wt = w[t % 16]
        kt = k_ref[t, 0]
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + kt + wt
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = s0 + maj
        a, b, c, d, e, f, g, h = t1 + t2, a, b, c, d + t1, e, f, g
        if t < 48:
            # Rolling schedule: W[t+16] replaces W[t] in place.
            w1, w9, w14 = w[(t + 1) % 16], w[(t + 9) % 16], w[(t + 14) % 16]
            sg0 = _rotr(w1, 7) ^ _rotr(w1, 18) ^ (w1 >> np.uint32(3))
            sg1 = _rotr(w14, 17) ^ _rotr(w14, 19) ^ (w14 >> np.uint32(10))
            w[t % 16] = wt + sg0 + w9 + sg1
    for i, v in enumerate((a, b, c, d, e, f, g, h)):
        out_ref[i, :] = v + jnp.broadcast_to(h0_ref[i, :], (tile,))


def _kernel_looped(k_ref, h0_ref, block_ref, out_ref):
    """fori_loop formulation — interpret-mode only (see `_kernel`)."""

    def round_body(t, carry):
        state, w = carry
        a, b, c, d, e, f, g, h = (state[i] for i in range(8))
        i0 = t % 16
        wt = jax.lax.dynamic_index_in_dim(w, i0, 0, keepdims=False)
        kt = jax.lax.dynamic_index_in_dim(
            k_ref[:], t, 0, keepdims=False
        )[0]
        s1 = _rotr(e, 6) ^ _rotr(e, 11) ^ _rotr(e, 25)
        ch = (e & f) ^ (~e & g)
        t1 = h + s1 + ch + kt + wt
        s0 = _rotr(a, 2) ^ _rotr(a, 13) ^ _rotr(a, 22)
        maj = (a & b) ^ (a & c) ^ (b & c)
        t2 = s0 + maj
        state = jnp.stack([t1 + t2, a, b, c, d + t1, e, f, g])
        w1 = jax.lax.dynamic_index_in_dim(w, (t + 1) % 16, 0, keepdims=False)
        w9 = jax.lax.dynamic_index_in_dim(w, (t + 9) % 16, 0, keepdims=False)
        w14 = jax.lax.dynamic_index_in_dim(w, (t + 14) % 16, 0, keepdims=False)
        sg0 = _rotr(w1, 7) ^ _rotr(w1, 18) ^ (w1 >> np.uint32(3))
        sg1 = _rotr(w14, 17) ^ _rotr(w14, 19) ^ (w14 >> np.uint32(10))
        w = jax.lax.dynamic_update_index_in_dim(w, wt + sg0 + w9 + sg1, i0, 0)
        return state, w

    w = block_ref[:]  # [16, TILE]
    tile = w.shape[1]
    init = jnp.broadcast_to(h0_ref[:], (8, tile))
    state, _ = jax.lax.fori_loop(0, 64, round_body, (init, w))
    out_ref[:] = init + state


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def _single_block_pallas(
    block: jax.Array, interpret: bool = False, tile: int = LANE_TILE
) -> jax.Array:
    b = block.shape[0]
    tile = min(tile, b)
    if b % tile:
        raise ValueError(f"batch {b} must divide by the lane tile {tile}")
    blk_t = block.astype(jnp.uint32).T  # [16, B]
    out = pl.pallas_call(
        _kernel_looped if interpret else _kernel,
        grid=(b // tile,),
        in_specs=[
            pl.BlockSpec((64, 1), lambda i: (0, 0)),  # K, replicated
            pl.BlockSpec((8, 1), lambda i: (0, 0)),  # H0, replicated
            pl.BlockSpec((16, tile), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((8, tile), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((8, b), jnp.uint32),
        interpret=interpret,
    )(
        jnp.asarray(_K).reshape(64, 1),
        jnp.asarray(_H0).reshape(8, 1),
        blk_t,
    )
    return out.T


def sha256_single_block_pallas(
    block: jax.Array, interpret: bool = False, tile: int | None = None
) -> jax.Array:
    """uint32[B, 16] pre-padded block → uint32[B, 8] digest.

    ``tile`` overrides the lanes-per-grid-step (default: CTMR_SHA_TILE
    env var, else LANE_TILE); must be a positive multiple of 128."""
    if tile is None:
        tile = lane_tile()
    elif tile < 128 or tile % 128:
        raise ValueError(f"tile must be a multiple of 128, got {tile}")
    return _single_block_pallas(block, interpret=interpret, tile=tile)


def sha256_fingerprint64_pallas(
    block: jax.Array, interpret: bool = False
) -> jax.Array:
    """Low 128 bits of the digest: uint32[B, 4] (dedup-key path)."""
    return sha256_single_block_pallas(block, interpret=interpret)[..., 4:]
