"""The plain reference: exact answers over the seeded points in numpy, with
nothing taken from the program under test.

    select   every point inside the closed query window
             (lx <= x <= hx and ly <= y <= hy), as sorted ids;
    nearest  the smallest squared distance dx*dx + dy*dy, each step
             rounded to float32 as the configuration's data is stored,
             and every id at that distance.

Both scan an x-sorted copy of the points: a window reads only the x-slab
it spans, and a nearest-point search widens a square box around the query
until the best distance found lies inside it.  Each answer is the brute
force over every point the box could not rule out.

``Reference(points, precision="bfloat16")`` is the control: the same
reference with every coordinate and every arithmetic step rounded to
bfloat16, the next precision below the configuration's float32.
"""
from __future__ import annotations

from typing import List, Tuple

import ml_dtypes
import numpy as np

# the nearest-point box must beat the best distance by more than rounding
# (float32: a few ulps, 1e-6 relative; bfloat16: 2**-8 per step)
_MARGIN = {"float32": 1e-5, "bfloat16": 0.05}


class Reference:
    def __init__(self, points: np.ndarray, precision: str = "float32"):
        if precision not in _MARGIN:
            raise ValueError(f"unknown precision {precision!r}")
        self.precision = precision
        pts = self._round(np.asarray(points, np.float32))
        order = np.argsort(pts[:, 0], kind="stable")
        self.x = pts[order, 0]
        self.y = pts[order, 1]
        self.ids = order.astype(np.int64)
        self.n = len(pts)

    def _round(self, a: np.ndarray) -> np.ndarray:
        """float32 values as this reference computes them."""
        if self.precision == "bfloat16":
            return a.astype(ml_dtypes.bfloat16).astype(np.float32)
        return a

    def _slab(self, x0: float, x1: float) -> slice:
        """The sorted points with x0 <= x <= x1, and perhaps a few more: the
        bounds are rounded outward to float32, since a float64 bound would
        make numpy cast every x to search."""
        lo, hi = np.float32(x0), np.float32(x1)
        if lo > x0:
            lo = np.nextafter(lo, np.float32(-np.inf))
        if hi < x1:
            hi = np.nextafter(hi, np.float32(np.inf))
        return slice(int(np.searchsorted(self.x, lo, "left")),
                     int(np.searchsorted(self.x, hi, "right")))

    def select(self, rects: np.ndarray) -> List[np.ndarray]:
        """(m, 4) windows (lx, ly, hx, hy) → m sorted id arrays.  The rows
        of one call share one x-slab, so pass a request's tiles together."""
        r = self._round(np.asarray(rects, np.float32))
        sl = self._slab(r[:, 0].min(), r[:, 2].max())
        x, y, ids = self.x[sl], self.y[sl], self.ids[sl]
        keep = (y >= r[:, 1].min()) & (y <= r[:, 3].max())
        x, y, ids = x[keep], y[keep], ids[keep]
        return [np.sort(ids[(x >= lx) & (x <= hx) & (y >= ly) & (y <= hy)])
                for lx, ly, hx, hy in r]

    def _sq_dist(self, x, y, qx, qy) -> np.ndarray:
        dx = self._round(np.abs(x - qx))
        dy = self._round(np.abs(y - qy))
        return self._round(self._round(dx * dx) + self._round(dy * dy))

    def nearest(self, q: np.ndarray) -> Tuple[np.ndarray, List[np.ndarray]]:
        """(m, 2) points → (squared distances (m,) float32, for each point
        the sorted ids of every data point at that distance)."""
        q = self._round(np.asarray(q, np.float32))
        d_out = np.empty(len(q), np.float32)
        tied: List[np.ndarray] = []
        r0 = 2.0 / np.sqrt(max(self.n, 1))
        for i, (qx, qy) in enumerate(q):
            r = r0
            while True:
                sl = self._slab(qx - r, qx + r)
                x, y, ids = self.x[sl], self.y[sl], self.ids[sl]
                box = np.abs(y.astype(np.float64) - qy) <= r
                full = sl.stop - sl.start == self.n and box.all()
                if box.any():
                    d = self._sq_dist(x[box], y[box], qx, qy)
                    best = d.min()
                    # every point outside the box lies farther than r
                    if best * (1 + _MARGIN[self.precision]) < r * r or full:
                        d_out[i] = best
                        tied.append(np.sort(ids[box][d == best]))
                        break
                elif full:
                    raise ValueError("nearest() over an empty point set")
                r *= 2.0
        return d_out, tied
