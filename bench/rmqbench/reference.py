"""The plain reference RMQ that decides ``correct``, and its control.

The reference imports nothing of the program. It answers a batch of (l, r)
with the leftmost minimum over ``x[l..r]`` inclusive, the rule the
configurations state, by the textbook blocked method in numpy:

* ``x`` is cut into blocks of ``BLOCK`` elements; each block's minimum and
  the index of its first occurrence are kept;
* a doubling table over the block minima answers whole blocks in O(1): the
  two overlapping windows of width 2^k, the left one preferred on ties;
* the one or two partial blocks at the ends are scanned in full.

Candidates are taken left to right (left partial, whole blocks, right
partial) and a later one replaces an earlier only when strictly smaller, so
the index returned is the leftmost minimum. ``bench/test_bench_harness.py``
holds it to a per-query ``np.argmin`` scan.

``control_values`` is the control of the comparison: the same values
rounded to bfloat16, the precision below float32 that a later change might
be tempted to store. Answers computed over it must fail the comparison.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

BLOCK = 32
_CHUNK = 1 << 15  # queries per vectorised step (bounds the gather's memory)


class Reference:
    """Blocked RMQ over a host copy of ``x`` (float values only)."""

    def __init__(self, x: np.ndarray):
        x = np.ascontiguousarray(x)
        if x.ndim != 1 or not np.issubdtype(x.dtype, np.floating):
            raise ValueError(f"reference wants a 1-D float array, got {x.dtype}{x.shape}")
        n = x.shape[0]
        nb = -(-n // BLOCK)
        # Pad with +inf: a pad never beats a real value, and no query reaches it.
        self.x = np.full(nb * BLOCK, np.inf, x.dtype)
        self.x[:n] = x
        self.n = n
        blocks = self.x.reshape(nb, BLOCK)
        first = blocks.argmin(axis=1)
        self.bidx = [(np.arange(nb) * BLOCK + first).astype(np.int64)]
        self.bval = [blocks[np.arange(nb), first]]
        k = 1
        while (1 << k) <= nb:
            half = 1 << (k - 1)
            lv, rv = self.bval[-1][:-half], self.bval[-1][half:]
            li, ri = self.bidx[-1][:-half], self.bidx[-1][half:]
            take_left = lv <= rv
            self.bval.append(np.where(take_left, lv, rv))
            self.bidx.append(np.where(take_left, li, ri))
            k += 1

    def query(self, l, r):
        """Leftmost-minimum (idx int32, val) for each (l, r)."""
        l = np.asarray(l, np.int64)
        r = np.asarray(r, np.int64)
        if l.shape != r.shape or l.ndim != 1:
            raise ValueError("l, r must be equal-length 1-D arrays")
        if l.size and (l.min() < 0 or r.max() >= self.n or np.any(l > r)):
            raise ValueError("query bounds outside 0 <= l <= r < n")
        idx = np.empty(l.shape, np.int64)
        val = np.empty(l.shape, self.x.dtype)
        for s in range(0, l.size, _CHUNK):
            idx[s : s + _CHUNK], val[s : s + _CHUNK] = self._query(l[s : s + _CHUNK], r[s : s + _CHUNK])
        return idx.astype(np.int32), val

    def _scan(self, base, lo, hi):
        """Leftmost min of x[base + j] for lo <= j <= hi, per query."""
        j = np.arange(BLOCK)
        seg = self.x[base[:, None] + j]
        seg = np.where((j >= lo[:, None]) & (j <= hi[:, None]), seg, np.inf)
        pos = seg.argmin(axis=1)
        return base + pos, seg[np.arange(base.size), pos]

    def _query(self, l, r):
        bl, br = l // BLOCK, r // BLOCK
        same = bl == br
        # Left partial: from l to the end of its block (or to r).
        idx, val = self._scan(bl * BLOCK, l - bl * BLOCK, np.where(same, r - bl * BLOCK, BLOCK - 1))
        # Whole blocks strictly between the two partial blocks.
        cnt = br - bl - 1
        mid = cnt >= 1
        if mid.any():
            a, b, c = bl[mid] + 1, br[mid] - 1, cnt[mid]
            k = np.floor(np.log2(c)).astype(np.int64)
            while np.any((1 << (k + 1)) <= c):  # guard float rounding of log2
                k = np.where((1 << (k + 1)) <= c, k + 1, k)
            lv = np.empty(a.size, self.x.dtype)
            li = np.empty(a.size, np.int64)
            rv = np.empty(a.size, self.x.dtype)
            ri = np.empty(a.size, np.int64)
            for kk in np.unique(k):
                sel = k == kk
                lv[sel] = self.bval[kk][a[sel]]
                li[sel] = self.bidx[kk][a[sel]]
                rv[sel] = self.bval[kk][b[sel] - (1 << kk) + 1]
                ri[sel] = self.bidx[kk][b[sel] - (1 << kk) + 1]
            take_left = lv <= rv
            mv, mi = np.where(take_left, lv, rv), np.where(take_left, li, ri)
            better = mv < val[mid]
            val[mid] = np.where(better, mv, val[mid])
            idx[mid] = np.where(better, mi, idx[mid])
        # Right partial: from the start of r's block to r.
        two = ~same
        if two.any():
            base = br[two] * BLOCK
            ri, rv = self._scan(base, np.zeros(base.size, np.int64), r[two] - base)
            better = rv < val[two]
            val[two] = np.where(better, rv, val[two])
            idx[two] = np.where(better, ri, idx[two])
        return idx, val


def control_values(x: np.ndarray) -> np.ndarray:
    """``x`` rounded to bfloat16 and widened back: the control's values."""
    return np.asarray(x).astype(ml_dtypes.bfloat16).astype(np.float32)


def wrong_answers(ref: Reference, l, r, idx, val) -> int:
    """Queries whose (idx, val) differ from the reference's."""
    gi, gv = ref.query(l, r)
    return int(np.count_nonzero((np.asarray(idx) != gi) | (np.asarray(val) != gv)))
