"""Closed-loop chat: ``clients`` users, each of whom sends a new request as
soon as its reply is complete.

Mix keys: ``arrivals: {"kind": "closed", "clients": n}``; ``prompt_len``
and ``output_len``, length distributions (``harness/lengths.py``); token
ids uniform over the vocabulary, no shared prefixes.  Lengths come from
the stratified grid, so every seed gets the same work in its own order.
The first ``clients`` requests, which are in flight when the measurement
starts, get the remaining lengths a closed loop in steady state would
have in flight (``lengths.residual_grid``), in a seed-drawn order.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench.harness.lengths import Stratified, length_grid, residual_grid


class Chat:
    def __init__(self, seed: int, mix: Dict[str, Any], vocab: int):
        arr = mix["arrivals"]
        if arr.get("kind") != "closed":
            raise ValueError(f"chat is a closed loop, not {arr!r}")
        self.seed, self.vocab = int(seed), int(vocab)
        self.clients = int(arr["clients"])
        prompts = length_grid(mix["prompt_len"])
        outputs = length_grid(mix["output_len"])
        self.max_prompt = int(prompts.max())
        self.max_output = int(outputs.max())
        cap = mix.get("engine", {}).get("max_len")
        if cap is not None and self.max_prompt + self.max_output > cap:
            raise ValueError(f"a request can reach {self.max_prompt} + "
                             f"{self.max_output} positions; the engine "
                             f"holds {cap}")
        self._plen = Stratified(prompts, seed, 0)
        self._olen = Stratified(outputs, seed, 1)
        rng = np.random.default_rng([self.seed, 13])
        self._first: List[int] = rng.permutation(
            residual_grid(outputs, self.clients)).tolist()

    def arrival(self, i: int) -> Optional[float]:
        """None: a closed loop sends when a client's reply completes."""
        return None

    def lengths(self, i: int) -> Tuple[int, int]:
        out = self._first[i] if i < self.clients else self._olen[i]
        return self._plen[i], int(out)

    def request(self, i: int) -> Tuple[np.ndarray, int]:
        """(prompt token ids, output length) of the i-th request sent."""
        plen, out = self.lengths(i)
        rng = np.random.default_rng([self.seed, 0, i])
        return rng.integers(0, self.vocab, plen, dtype=np.int32), out

    def warm_prompts(self) -> List[np.ndarray]:
        """One prompt of the longest length: its chunks take every chunk
        start the traffic can reach."""
        rng = np.random.default_rng([self.seed, 99])
        return [rng.integers(0, self.vocab, self.max_prompt, dtype=np.int32)]


def make(seed: int, mix: Dict[str, Any], vocab: int) -> Chat:
    return Chat(seed, mix, vocab)

