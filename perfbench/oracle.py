"""Plain reference for exact k-th-nearest-neighbour distances.

Independent of the program under test: numpy only, nothing imported from
the program, nothing taken from what it built. The answer for a query q is
the k-th smallest squared distance over ALL points, each summed as
``(dx*dx + dy*dy) + dz*dz`` in float32 (the order the program's kernels
guarantee), then a correctly rounded float32 square root. Self-joins count
the query point itself (distance 0), as the reference CUDA code does.

Brute force over every point is O(N) per query, too slow for tens of
thousands of queries at 12.5M points, so the search is restricted to a cube
of cells around q and widened until it provably holds the answer: every
point outside the cube differs from q by more than r along one axis, so its
float32 squared distance is at least fl(r*r) (rounding is monotone and the
three non-negative terms only add). A k-th squared distance strictly below
fl(r*r) found inside the cube is therefore the k-th over all points.

``precision="bf16"`` is the control: the same search with every operation
of the distance rounded to bfloat16 -- the precision step below float32
that would tempt a faster kernel. It must fail the comparison.
"""

from __future__ import annotations

import numpy as np

try:  # bfloat16 for numpy ships with jax; only the control needs it
    from ml_dtypes import bfloat16 as _BF16
except ImportError:  # pragma: no cover - jax always brings ml_dtypes
    _BF16 = None

#: reading for an answer that is NaN, negative, or missing altogether
WORST_ULP = (1 << 31) - 1


class GridReference:
    """Points bucketed into a uniform grid of cells over their bounding box."""

    def __init__(self, points: np.ndarray, k: int):
        pts = np.ascontiguousarray(points, np.float32)
        if pts.ndim != 2 or pts.shape[1] != 3:
            raise ValueError(f"points must be [N, 3], got {pts.shape}")
        if not 1 <= k <= len(pts):
            raise ValueError(f"need 1 <= k <= N, got k={k}, N={len(pts)}")
        self.k = int(k)
        self.lo = pts.min(axis=0).astype(np.float64)
        hi = pts.max(axis=0).astype(np.float64)
        extent = float(max((hi - self.lo).max(), 1e-30))
        # expected radius of the k-th neighbour if the points filled the box
        # evenly; cells half that wide keep a query's cube near 5^3 cells
        self.r0 = extent * (self.k / (len(pts) * 4.19)) ** (1 / 3)
        self.g = int(np.clip(2 * extent / self.r0, 1, 512))
        self.cell = extent / self.g
        cid = self._cells(pts)
        flat = (cid[:, 0] * self.g + cid[:, 1]) * self.g + cid[:, 2]
        order = np.argsort(flat, kind="stable")
        self.pts = pts[order]
        self.starts = np.searchsorted(flat[order],
                                      np.arange(self.g ** 3 + 1))

    def _cells(self, x: np.ndarray) -> np.ndarray:
        c = np.floor((np.asarray(x, np.float64) - self.lo) / self.cell)
        return np.clip(c, 0, self.g - 1).astype(np.int64)

    def _candidates(self, q: np.ndarray, r: float) -> np.ndarray:
        lo = self._cells(q - r)
        hi = self._cells(q + r)
        g = self.g
        parts = []
        for ix in range(lo[0], hi[0] + 1):
            for iy in range(lo[1], hi[1] + 1):
                base = (ix * g + iy) * g
                a = self.starts[base + lo[2]]
                b = self.starts[base + hi[2] + 1]
                if b > a:
                    parts.append(self.pts[a:b])
        return np.concatenate(parts) if parts else self.pts[:0]

    def kth_dist2(self, q: np.ndarray, precision: str = "f32") -> np.float32:
        """k-th smallest squared distance from one query to all points."""
        q = np.asarray(q, np.float32)
        r = self.r0
        while True:
            cand = self._candidates(q, r)
            covers_all = len(cand) == len(self.pts)
            if len(cand) >= self.k:
                d2 = _sqdist(q, cand, precision)
                kth = np.partition(d2, self.k - 1)[self.k - 1]
                if covers_all or kth < np.float32(r) * np.float32(r):
                    return np.float32(kth)
            r *= 2.0

    def kth_dists(self, queries: np.ndarray,
                  precision: str = "f32") -> np.ndarray:
        """f32 k-th-NN distance for each query row."""
        queries = np.asarray(queries, np.float32).reshape(-1, 3)
        d2 = np.array([self.kth_dist2(q, precision) for q in queries],
                      np.float32)
        return np.sqrt(d2)


def _sqdist(q: np.ndarray, p: np.ndarray, precision: str) -> np.ndarray:
    if precision == "f32":
        d = q[None, :] - p
        return (d[:, 0] * d[:, 0] + d[:, 1] * d[:, 1]) + d[:, 2] * d[:, 2]
    if precision == "bf16":
        if _BF16 is None:
            raise RuntimeError("the bf16 control needs ml_dtypes")
        d = q.astype(_BF16)[None, :] - p.astype(_BF16)
        s = (d[:, 0] * d[:, 0] + d[:, 1] * d[:, 1]) + d[:, 2] * d[:, 2]
        return s.astype(np.float32)
    raise ValueError(f"unknown precision {precision!r}")


def ulp_gap(got, want) -> np.ndarray:
    """Per-row distance in float32 units in the last place (int64).

    Both sides are non-negative distances, whose bit patterns order like
    the values, so the gap is the difference of the int32 views; +inf sits
    just above the largest finite float. A NaN or negative answer, or a
    NaN in the reference, reads ``WORST_ULP``."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    gap = np.abs(got.view(np.int32).astype(np.int64)
                 - want.view(np.int32).astype(np.int64))
    bad = np.isnan(got) | np.isnan(want) | (got < 0) | (want < 0)
    gap[bad] = WORST_ULP
    return np.minimum(gap, WORST_ULP)


def max_ulp(got, want) -> int:
    """Widest gap over all rows; ``WORST_ULP`` when the shapes differ."""
    got = np.asarray(got)
    want = np.asarray(want)
    if got.shape != want.shape:
        return WORST_ULP
    return int(ulp_gap(got, want).max()) if got.size else 0
