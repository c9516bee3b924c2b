"""What one growth of the dedup table has to move on the device, from
the configuration alone (beside ``kernel_cost.py`` and
``snapshot_cost.py``): whatever implements the rehash, the share of the
roofline reads the same work.
"""

ROW_BYTES = 32  # a slot of the dedup table (configs/: "32-byte bucket rows")


def table_double(table_bits: int) -> dict:
    """A growth doubles the table: every row of the table that
    ``tableBits`` builds is read once and every row of the doubled
    table written once, and no arithmetic is counted (hashing a key's
    two words again is a few integer operations a row, and no peak for
    32-bit integer work is published: ``peaks.py``)."""
    return {"hbm_bytes": ((1 << table_bits) + (1 << (table_bits + 1)))
            * ROW_BYTES}
