"""What one refresh of a query-plane replica has to move on the device,
from the configuration alone (beside ``kernel_cost.py``): whatever
implements the copy, the share of the roofline reads the same work.
"""

ROW_BYTES = 32  # a slot of the dedup table (configs/: "32-byte bucket rows")


def table_copy(table_bits: int) -> dict:
    """A replica is a copy of the live table: every row read once and
    written once, and no arithmetic."""
    return {"hbm_bytes": 2 * (1 << table_bits) * ROW_BYTES}
