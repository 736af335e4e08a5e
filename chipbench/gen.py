"""The one traffic and data generator.  A configuration file names the data
(``distribution``, ``n_points``); a traffic mix is a data file of
parameters (``traffic/<mix>.json``) that this module reads:

    arrivals   "open": Poisson arrivals at ``rate_per_s`` requests/s, each
               timed from its due time; "closed": ``clients`` callers that
               each send their next request when the last one resolves.
    query      "viewport": ``tiles`` [tx, ty] adjacent square tiles, one
               select row each, the tile's share of the unit square drawn
               log-uniform in 10**``tile_selectivity_log10`` [lo, hi];
               "point": one uniform point per row.
    rows       rows per request (a viewport always sends tx * ty).

Every draw comes from ``--seed`` through ``rng(seed, stream, ...)``, so the
same seed gives the same data and requests.  The open loop gives every
seed the same multiset of gaps and tile sizes, in another order: seeds
change where the work lands, not how much of it there is.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import numpy as np

# independent random streams of one run
DATA, WINDOW, WARMUP, SAMPLE = 0, 1, 2, 3


def rng(seed: int, stream: int, *extra: int) -> np.random.Generator:
    """A generator for one stream of one seed (any whole number)."""
    return np.random.default_rng([stream, seed % (1 << 64), *extra])


def points(cfg: Dict, seed: int) -> np.ndarray:
    """The configuration's data: (n_points, 2) float32 in the unit square."""
    if cfg["distribution"] != "uniform":
        raise ValueError(f"unknown distribution {cfg['distribution']!r}")
    return rng(seed, DATA).random((cfg["n_points"], 2), dtype=np.float32)


def rows_per_request(mix: Dict) -> int:
    if mix["query"] == "viewport":
        tx, ty = mix["tiles"]
        return tx * ty
    return int(mix["rows"])


def _stratified(n: int, g: np.random.Generator) -> np.ndarray:
    """n midpoint quantiles of U(0, 1), shuffled: the same values for every
    seed, in the seed's order."""
    return (g.permutation(n) + 0.5) / n


def _viewports(mix: Dict, n: int, g: np.random.Generator,
               stratified: bool) -> np.ndarray:
    """(n, tx*ty, 4) float32 tile rects; each request's tiles are adjacent,
    so neighbouring tiles share their edge exactly."""
    tx, ty = mix["tiles"]
    lo, hi = mix["tile_selectivity_log10"]
    u = _stratified(n, g) if stratified else g.random(n)
    side = np.sqrt(10.0 ** (lo + (hi - lo) * u))
    ox = g.random(n) * (1.0 - tx * side)
    oy = g.random(n) * (1.0 - ty * side)
    i, j = np.meshgrid(np.arange(tx), np.arange(ty), indexing="ij")
    i, j = i.ravel(), j.ravel()
    s = side[:, None]
    return np.stack([ox[:, None] + i * s, oy[:, None] + j * s,
                     ox[:, None] + (i + 1) * s, oy[:, None] + (j + 1) * s],
                    axis=-1).astype(np.float32)


def requests(mix: Dict, n: int, g: np.random.Generator,
             stratified: bool = True) -> np.ndarray:
    """(n, rows, width) float32 query rows of n requests; ``stratified``
    draws the viewport sizes as shuffled quantiles (see ``open_schedule``)."""
    if mix["query"] == "viewport":
        return _viewports(mix, n, g, stratified)
    if mix["query"] == "point":
        return g.random((n, rows_per_request(mix), 2), dtype=np.float32)
    raise ValueError(f"unknown query kind {mix['query']!r}")


def open_schedule(mix: Dict, seconds: float, seed: int, stream: int = WINDOW
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Poisson arrivals over ``seconds``: (due offsets (n,) in seconds from
    the window's start, ascending, the last one at ``seconds``; requests
    (n, rows, width)).  n is the rate times the window; the gaps are the
    exponential distribution's midpoint quantiles, shuffled by the seed."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    g = rng(seed, stream)
    gaps = -np.log1p(-_stratified(n, g))
    due = np.cumsum(gaps)
    due *= seconds / due[-1]
    return due, requests(mix, n, g)


def closed_requests(mix: Dict, seed: int, stream: int = WINDOW
                    ) -> Callable[[int], np.ndarray]:
    """Request i of a closed loop, drawn on demand (the number sent depends
    on the system's speed).  Safe to call from several threads: each
    request has a generator of its own."""
    def make(i: int) -> np.ndarray:
        return requests(mix, 1, rng(seed, stream, i), stratified=False)[0]
    return make
