"""What one fixed-window INCRBY needs to move, whatever implements it.

Per decision: one input row in the submit_rows layout (uint32[6]: fp_lo,
fp_hi, hits, limit, divider, jitter) = 24 B; one slab row read and one
written (ROW_WIDTH = 8 uint32, ops/slab.py) = 2 x 32 B; one uint32
verdict = 4 B. 92 B in all. The step's integer compares and adds are far
below any op/s peak, so the least time is bytes / HBM bandwidth; a kernel
that reads a whole 128-way set per decision shows as a low share, which
is the headroom a later change can take."""

from __future__ import annotations

INPUT_ROW_BYTES = 6 * 4
SLAB_ROW_BYTES = 8 * 4
VERDICT_BYTES = 4
BYTES_PER_DECISION = INPUT_ROW_BYTES + 2 * SLAB_ROW_BYTES + VERDICT_BYTES


def least_seconds(decisions: int, peak: dict) -> float:
    """The least time the chip could take for `decisions` INCRBYs: the
    byte model over the HBM peak (bytes bound it; say so where reported)."""
    return decisions * BYTES_PER_DECISION / peak["hbm_bytes_per_s"]


def roofline_percent(decisions: int, device_seconds: float, peak: dict) -> float:
    """Share of the roofline, in %: least time over measured device time."""
    return 100.0 * least_seconds(decisions, peak) / device_seconds
