"""What a kernel has to do for one call, from its shapes: operations and
bytes, kept here so that no later change to the program can move them.
"""


def sha256_single_block(lanes: int) -> dict:
    """One SHA-256 compression per lane (FIPS 180-4 section 6.2.2), as
    32-bit integer operations, and the HBM traffic a fused kernel cannot
    avoid: the 16-word block in, the 8-word digest out.

    Per lane: 48 message-schedule steps of sigma0 and sigma1 (2 rotates
    of 3 ops, 1 shift, 2 xors each) plus 3 adds, and 64 rounds of Sigma0
    and Sigma1 (3 rotates, 2 xors each), Ch (3), Maj (4) and 7 adds, and
    8 adds into the state."""
    small_sigma = 2 * 3 + 1 + 2
    big_sigma = 3 * 3 + 2
    per_lane = 48 * (2 * small_sigma + 3) + 64 * (2 * big_sigma + 3 + 4 + 7) + 8
    return {"int32_ops": per_lane * lanes, "hbm_bytes": (16 + 8) * 4 * lanes}
