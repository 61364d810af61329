"""Continuous-batching scheduler: slot lifecycle + per-request sampling.

The scheduler owns *bookkeeping only* (no model code): a FIFO of pending
requests, a fixed table of ``max_batch`` slots, and the per-slot arrays
(position, temperature, top-k, seed, tokens-generated) that the engine
feeds to its fixed-shape jitted decode step.  Admission fills free slots,
eviction frees them on EOS / max-new-tokens / cache exhaustion, and the
batch advances every live slot in lockstep even though each sits at its
own sequence position (the per-row ``pos`` form of ``lm_decode_step``).

Determinism: a request's sampling key stream is
``fold_in(PRNGKey(seed), n_generated)`` — a function of the request alone,
never of its slot index or of which other requests share the batch — so
results are identical under any admission order or batch packing (the
property pinned by tests/test_serve.py).
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["SamplingParams", "Request", "Scheduler", "sample_tokens"]


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request decode controls. ``temperature<=0`` = greedy;
    ``top_k=0`` = full vocab."""
    temperature: float = 0.0
    top_k: int = 0
    max_new_tokens: int = 32
    seed: int = 0


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                       # (T,) int32
    sampling: SamplingParams
    submit_t: float = 0.0
    prefill_t: Optional[float] = None        # its first chunk dispatched
    first_token_t: Optional[float] = None
    finish_t: Optional[float] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    finish_reason: Optional[str] = None      # "eos" | "length" | "cache_full"

    @property
    def done(self) -> bool:
        return self.finish_reason is not None

    @property
    def latency_s(self) -> Optional[float]:
        if self.finish_t is None:
            return None
        return self.finish_t - self.submit_t


def sample_tokens(logits, temperature, top_k, seeds, n_gen,
                  any_sampled: bool = True, any_top_k: bool = True):
    """Vectorized per-slot sampling (jit-friendly).

    logits: (B, V); temperature/top_k/seeds/n_gen: (B,).  Greedy rows take
    argmax; sampled rows draw from the temperature-scaled (optionally
    top-k-masked) categorical with key ``fold_in(PRNGKey(seed), n_gen)``.
    ``any_sampled``/``any_top_k`` are *static* fast-path switches: the
    engine passes False when no live slot samples (skips the categorical)
    or none uses top-k (skips the full-vocab sort on the hot path).
    """
    B, V = logits.shape
    lf = logits.astype(jnp.float32)
    greedy = jnp.argmax(lf, axis=-1).astype(jnp.int32)
    if not any_sampled:
        return greedy
    masked = lf
    if any_top_k:
        k = jnp.where(top_k > 0, jnp.minimum(top_k, V), V)      # (B,)
        # Rank every vocab entry (stable sort: ties broken toward the
        # lower index) and keep exactly the k best — a >= threshold test
        # would admit *every* logit tied with the k-th value, inflating
        # the candidate set beyond k.
        order = jnp.argsort(-lf, axis=-1, stable=True)           # (B, V)
        ranks = jnp.argsort(order, axis=-1, stable=True)         # rank of v
        masked = jnp.where(ranks < k[:, None], lf, -jnp.inf)
    scaled = masked / jnp.maximum(temperature, 1e-6)[:, None]

    def draw(seed, n, row):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), n)
        return jax.random.categorical(key, row)

    sampled = jax.vmap(draw)(seeds, n_gen, scaled).astype(jnp.int32)
    return jnp.where(temperature > 0.0, sampled, greedy)


class Scheduler:
    """Fixed-slot continuous batching (admit / decode / evict)."""

    def __init__(self, max_batch: int, max_len: int,
                 eos_id: Optional[int] = None):
        self.max_batch = max_batch
        self.max_len = max_len
        self.eos_id = eos_id
        self.queue: Deque[Request] = deque()
        self.slots: List[Optional[Request]] = [None] * max_batch
        # Per-slot state mirrored into the jitted step each call.
        self.pos = np.zeros(max_batch, np.int32)
        self.cur_tok = np.zeros(max_batch, np.int32)
        self.temp = np.zeros(max_batch, np.float32)
        self.top_k = np.zeros(max_batch, np.int32)
        self.seeds = np.zeros(max_batch, np.int32)
        self.n_gen = np.zeros(max_batch, np.int32)

    # ---- queue / admission -------------------------------------------------
    def submit(self, req: Request) -> None:
        self.queue.append(req)

    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self.slots)

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or self.n_active > 0

    def admissions(self) -> List[Tuple[int, Request]]:
        """Pop queued requests into free slots (FIFO)."""
        out = []
        for i in range(self.max_batch):
            if self.slots[i] is None and self.queue:
                out.append((i, self.queue.popleft()))
        return out

    def place(self, slot: int, req: Request, first_token: int,
              pos: int) -> bool:
        """Install a prefilled request: record its first sampled token and
        arm the slot at ``pos`` (= prompt length).  Returns True when the
        request already finished (1-token budget or immediate EOS)."""
        req.tokens.append(first_token)
        req.first_token_t = time.perf_counter()
        self.slots[slot] = req
        self.pos[slot] = pos
        self.cur_tok[slot] = first_token
        self.temp[slot] = req.sampling.temperature
        self.top_k[slot] = req.sampling.top_k
        self.seeds[slot] = req.sampling.seed
        self.n_gen[slot] = 1
        return self._maybe_finish(slot, first_token)

    # ---- batched views -----------------------------------------------------
    def batch_arrays(self):
        """(tok (B,1), pos (B,), temp, top_k, seeds, n_gen) device arrays.
        Inactive slots are clamped in-range; their (masked, soon to be
        overwritten) cache writes land in rows no live request reads."""
        pos = np.minimum(self.pos, self.max_len - 1)
        return (jnp.asarray(self.cur_tok[:, None]), jnp.asarray(pos),
                jnp.asarray(self.temp), jnp.asarray(self.top_k),
                jnp.asarray(self.seeds), jnp.asarray(self.n_gen))

    # ---- step / eviction ---------------------------------------------------
    def record_step(self, next_tok: np.ndarray) -> List[Request]:
        """Account one decode step: per live slot, the fed token advanced
        the cache to ``pos`` and ``next_tok[slot]`` was sampled.  Returns
        requests that finished (and frees their slots)."""
        finished = []
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            tok = int(next_tok[i])
            self.pos[i] += 1
            req.tokens.append(tok)
            self.cur_tok[i] = tok
            self.n_gen[i] += 1
            if self._maybe_finish(i, tok):
                finished.append(req)
        return finished

    def _maybe_finish(self, slot: int, tok: int) -> bool:
        req = self.slots[slot]
        if self.eos_id is not None and tok == self.eos_id:
            req.finish_reason = "eos"
        elif len(req.tokens) >= req.sampling.max_new_tokens:
            req.finish_reason = "length"
        elif self.pos[slot] >= self.max_len:
            req.finish_reason = "cache_full"   # no slot left to write to
        else:
            return False
        req.finish_t = time.perf_counter()
        self.slots[slot] = None
        # Zero *all* per-slot state: a freed slot must not keep decoding
        # stale tokens at a stale position (its masked writes still land in
        # the clamped cache row every step until re-admission), and the
        # paged allocator keys live-row detection on pos/cur_tok being zero.
        self.pos[slot] = 0
        self.cur_tok[slot] = 0
        self.temp[slot] = 0.0
        self.top_k[slot] = 0
        self.seeds[slot] = 0
        self.n_gen[slot] = 0
        return True
