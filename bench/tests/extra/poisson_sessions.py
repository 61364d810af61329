"""Open-loop Poisson arrivals of requests from sessions that share a
prefix: the example of a traffic generator that a cell adds as a file of
its own (the tests copy it to ``bench/generators/``).

Mix keys: ``arrivals: {"kind": "poisson", "rate": r}`` (requests a
second); ``sessions: {"count": n, "prefix_len": p}``: request i belongs to
session i mod n, and its prompt is the session's p tokens followed by
``prompt_len`` tokens of its own; ``output_len``.  Gaps between arrivals
and lengths come from stratified grids, so every seed gets the same work.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench.harness.lengths import GRID, Stratified, length_grid


class PoissonSessions:
    def __init__(self, seed: int, mix: Dict[str, Any], vocab: int):
        arr = mix["arrivals"]
        if arr.get("kind") != "poisson":
            raise ValueError(f"poisson_sessions wants poisson arrivals, "
                             f"not {arr!r}")
        self.seed, self.vocab, self.clients = int(seed), int(vocab), 0
        u = (np.arange(GRID) + 0.5) / GRID
        self._gaps = Stratified(-np.log1p(-u) / float(arr["rate"]), seed, 2)
        self._times: List[float] = [0.0]
        ses = mix["sessions"]
        self.n_sessions, self.prefix_len = int(ses["count"]), \
            int(ses["prefix_len"])
        prompts = length_grid(mix["prompt_len"])
        self.max_prompt = self.prefix_len + int(prompts.max())
        self._plen = Stratified(prompts, seed, 0)
        self._olen = Stratified(length_grid(mix["output_len"]), seed, 1)
        rng = np.random.default_rng([self.seed, 17])
        self._prefixes = rng.integers(0, vocab, (self.n_sessions,
                                                 self.prefix_len), np.int32)

    def arrival(self, i: int) -> Optional[float]:
        while len(self._times) <= i:
            self._times.append(self._times[-1]
                               + self._gaps[len(self._times) - 1])
        return self._times[i]

    def request(self, i: int) -> Tuple[np.ndarray, int]:
        rng = np.random.default_rng([self.seed, 0, i])
        own = rng.integers(0, self.vocab, self._plen[i], dtype=np.int32)
        return (np.concatenate([self._prefixes[i % self.n_sessions], own]),
                self._olen[i])

    def warm_prompts(self) -> List[np.ndarray]:
        rng = np.random.default_rng([self.seed, 99])
        return [rng.integers(0, self.vocab, self.max_prompt, dtype=np.int32)]


def make(seed: int, mix: Dict[str, Any], vocab: int) -> PoissonSessions:
    return PoissonSessions(seed, mix, vocab)
