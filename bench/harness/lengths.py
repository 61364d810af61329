"""Length distributions on a fixed grid of quantiles, handed out in a
seed-drawn, stratified order: the pieces a traffic generator shares.

Every seed gets the same work in another order.  Lengths are a
distribution's quantiles on a grid of 256, cut into 8 strata of 32
neighbouring quantiles.  Requests come in blocks of 8, and block b takes
from every stratum the same member, the b-th of a fixed order that
alternates low and high members: so each block of 8 consecutive requests
holds the same 8 lengths for every seed, one from each eighth of the
distribution, and 32 blocks use the grid once.  The seed draws only the
order within each block, the token ids and the weights.  A window that
sees some blocks of requests then sees the same work for every seed.
"""
from __future__ import annotations

import statistics
from typing import Any, Dict, List

import numpy as np

GRID = 256          # quantiles of a distribution
STRATA = 8          # strata of the grid; a block of requests holds one each
BLOCK = GRID // STRATA   # members of a stratum, and blocks to use the grid


def _alternating(n: int) -> List[int]:
    """0..n-1 (n a power of two) in bit-reversed order: 0, n/2, n/4,
    3n/4, ...; every run of consecutive entries spreads over the range."""
    bits = n.bit_length() - 1
    return [int(format(i, f"0{bits}b")[::-1], 2) for i in range(n)]


MEMBER = _alternating(BLOCK)


def length_grid(dist: Dict[str, Any], n: int = GRID) -> np.ndarray:
    """``n`` values at the mid-quantiles of a length distribution, in
    ascending order: ``{"dist": "lognormal", "median", "sigma", "min",
    "max"}``, ``{"dist": "uniform", "min", "max"}`` or ``{"dist":
    "fixed", "value"}``."""
    kind = dist.get("dist")
    u = (np.arange(n) + 0.5) / n
    if kind == "lognormal":
        nd = statistics.NormalDist()
        z = np.array([nd.inv_cdf(float(p)) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
        lo, hi = dist["min"], dist["max"]
    elif kind == "uniform":
        lo, hi = dist["min"], dist["max"]
        x = lo + u * (hi - lo)
    elif kind == "fixed":
        x = lo = hi = np.full(n, dist["value"])
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def residual_grid(grid: np.ndarray, n: int) -> np.ndarray:
    """``n`` mid-quantiles of what is left of a length drawn from ``grid``
    when it is met at a uniformly random point of its life: the remaining
    length r has probability in proportion to the share of the grid at or
    above r (a renewal process's forward recurrence).  Closed loops start
    their first requests with these lengths, so their completions come as
    they would in steady state."""
    top = int(grid.max())
    r = np.arange(1, top + 1)
    w = (grid[None, :] >= r[:, None]).sum(axis=1).astype(float)
    cdf = np.cumsum(w) / w.sum()
    u = (np.arange(n) + 0.5) / n
    return r[np.searchsorted(cdf, u)]


class Stratified:
    """Values of a grid in blocks of ``STRATA``: block b holds member
    ``MEMBER[b mod BLOCK]`` of every stratum, in a seed-drawn order."""

    def __init__(self, grid: np.ndarray, seed: int, stream: int):
        if grid.size != GRID:
            raise ValueError(f"grid of {grid.size}, not {GRID}")
        self.grid = np.sort(grid)
        self.seed, self.stream = int(seed), int(stream)
        self._blocks: Dict[int, np.ndarray] = {}

    def block(self, b: int) -> np.ndarray:
        """The values of block ``b`` in its seed-drawn order."""
        if b not in self._blocks:
            rng = np.random.default_rng([self.seed, 11, self.stream, b])
            strata = rng.permutation(STRATA)
            self._blocks[b] = self.grid[strata * BLOCK + MEMBER[b % BLOCK]]
        return self._blocks[b]

    def __getitem__(self, i: int):
        b, j = divmod(int(i), STRATA)
        return self.block(b)[j].item()


def sample_indices(seed: int, n: int, k: int, must: List[int]) -> List[int]:
    """``k`` indices of ``range(n)`` drawn from ``seed``, with ``must``
    among them."""
    rng = np.random.default_rng([int(seed), 7])
    rest = [i for i in rng.permutation(n).tolist() if i not in must]
    return sorted(set(must) | set(rest[:max(0, k - len(set(must)))]))
