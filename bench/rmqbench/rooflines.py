"""Bytes each query path must move, computed from the queries alone.

The count is the least the algorithm reads and writes, whatever implements
it, so a kernel that packs or re-fetches cannot read above its roofline:

* fused short-path kernel, per query: the one or two 128-element rows that
  hold ``l`` and ``r`` (one when both lie in one block), at the value's
  width; the two interior candidates of 8 B (value and index) when whole
  blocks lie between the two rows; and 16 B for ``l``, ``r``, ``idx`` and
  ``val``;
* long path (doubling-table gathers), per query: two table cells and two
  values of 4 B each, and the same 16 B.

Both paths are bound by bytes (and by the latency of their gathers), not by
operations, so a share of the HBM roofline is the measure that applies.
"""

from __future__ import annotations

import numpy as np

KERNEL_BLOCK = 128  # elements per row the short-path kernel reads
IO_BYTES = 16  # l, r, idx, val at 4 B each


def fused_bytes(l, r, value_bytes: int = 4) -> int:
    """Bytes the fused short-path kernel must move for these queries."""
    bl = np.asarray(l, np.int64) // KERNEL_BLOCK
    br = np.asarray(r, np.int64) // KERNEL_BLOCK
    rows = np.where(bl == br, 1, 2)
    interior = np.where(br - bl >= 2, 2 * 8, 0)
    return int(np.sum(rows * KERNEL_BLOCK * value_bytes + interior + IO_BYTES))


def long_bytes(l, r) -> int:
    """Bytes the doubling-table long path must move for these queries."""
    return int(np.asarray(l).size) * (2 * 4 + 2 * 4 + IO_BYTES)
