"""ServeEngine: fused prefill + continuous batching over cached jit steps.

The serving counterpart of ``train.loop.Trainer``: a facade whose
``submit``/``step``/``drain`` drive the scheduler and whose ``events``
list mirrors ``Trainer.events`` (submit / prefill / request_done records
with latency and throughput fields; ``stats()`` aggregates them).

Compilation discipline — the former ``decode.py`` stub rebuilt ``jax.jit``
closures on every call; here every jitted function is a module-level
``repro.runtime.SegmentFn`` with the (frozen, hashable)
``LMConfig``/``QuantConfig`` as static arguments, so the trace cache is
keyed on ``(cfg, qcfg)`` + shapes, is shared by every engine, wrapper,
benchmark, and test in the process, and every retrace is accounted (a
revisited ``(cfg, qcfg)`` — e.g. a qcfg bucket switch — must hit the
cache, which benchmarks/runtime_unify.py asserts in CI):

  * ``_serve_step``   — fixed (max_batch, 1) decode + per-slot sampling;
    admission swaps one cache row (``_insert_row``) and never recompiles.
  * ``_prefill``      — fused single-pass ``lm_prefill``.  For purely
    positional caches (global attention, no ring buffer / recurrent
    state) prompts are right-padded to power-of-two buckets: padded cache
    slots sit beyond the causal mask until a later decode step overwrites
    them, so padding is numerically inert and the engine compiles one
    prefill per bucket instead of one per prompt length.
  * ``_decode_step``  — token-stepped fallback (encoder-decoder and
    frontend configs) and the parity oracle for the fused path.

:class:`PagedServeEngine` swaps the per-slot KV slabs for a global page
pool (``models.init_cache_paged``) managed by ``pages.PageAllocator``: a
request maps only the pages its length needs, prompts prefill one chunk
per ``step()`` interleaved with live decodes (``lm_prefill_chunk``), full
prompt pages are shared across requests by content (prefix cache), and
page pressure is resolved by LRU eviction of unreferenced cached pages or
LIFO preemption of the newest request.  Decode runs the same per-row
positions through ``_serve_step_paged`` with the (B, P) page table.

Both engines serve the dense projections MX-packed at rest
(``models.pack_serving_weights``) wherever the preset quantizes weights;
``stats()`` reports ``packed_weight_share`` and ``packed_weight_bytes``,
and the journal's ``pack_weights`` record holds both from construction.

For a model with experts the paged engine's decode step and prefill chunks
also return their routing counters, which the host reads with the sampled
tokens: ``decode_routing`` holds the last decode step's (moe_layers, held)
tokens routed to each expert held here, and the counters
``moe.held_tokens`` and ``moe.dropped`` (decode and prefill together) sit
in ``spans.counters`` and ``stats()``.
"""
from __future__ import annotations

import time
from collections import deque
from functools import partial
from typing import Any, Deque, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import QuantConfig
from repro.runtime import Journal, MemoryLedger, SegmentFn, Spans
from repro.models import (LMConfig, block_plan, chunk_supported, init_cache,
                          init_cache_paged, lm_decode_step,
                          lm_decode_step_routed, lm_prefill,
                          lm_prefill_chunk, pack_serving_weights,
                          paged_leaf_mask, prefill_supported)
from .pages import (PageAllocator, gather_prior, prefix_chain,
                    write_chunk_pages, zero_pages)
from .scheduler import Request, SamplingParams, Scheduler, sample_tokens

__all__ = ["ServeEngine", "PagedServeEngine"]


@partial(SegmentFn, static_argnums=(4, 5))
def _decode_step(params, cache, tok, pos, cfg: LMConfig, qcfg: QuantConfig):
    return lm_decode_step(params, cache, tok, pos, cfg, qcfg)


@partial(SegmentFn, static_argnums=(2, 3, 4))
def _prefill(params, tokens, cfg: LMConfig, qcfg: QuantConfig, max_len: int,
             logit_positions):
    return lm_prefill(params, tokens, cfg, qcfg, max_len, logit_positions)


# ``start`` is static: it fixes the chunk's absolute positions and the
# AttnSpec q_offset, both of which shape the rectangular flash grid.  Chunk
# starts are multiples of the page size, so the trace count is bounded by
# max_len / page_size, not by prompt diversity.
@partial(SegmentFn, static_argnums=(3, 4, 5))
def _prefill_chunk(params, tokens, prior, start: int, cfg: LMConfig,
                   qcfg: QuantConfig, logit_positions, kv_mask):
    return lm_prefill_chunk(params, tokens, prior, start, cfg, qcfg,
                            logit_positions, kv_mask)


# The engine rebinds its cache to the step result every call, so the input
# cache buffers are donated: XLA updates the KV/state arrays in place
# instead of copying the full (max_batch, max_len) cache per token (and
# per admission).  Donation is a no-op (with a one-time notice) on CPU.
@partial(SegmentFn, static_argnums=(4, 5, 10, 11), donate_argnums=(1,))
def _serve_step(params, cache, tok, pos, cfg: LMConfig, qcfg: QuantConfig,
                temp, top_k, seeds, n_gen, any_sampled: bool,
                any_top_k: bool):
    """One fixed-shape engine step: batched decode + per-slot sampling.
    The two static sampling switches add at most 4 traces per (cfg, qcfg)
    and keep the all-greedy hot path free of sort/categorical work."""
    logits, cache = lm_decode_step(params, cache, tok, pos, cfg, qcfg)
    nxt = sample_tokens(logits, temp, top_k, seeds, n_gen,
                        any_sampled, any_top_k)
    return nxt, cache


@partial(SegmentFn, static_argnums=(5, 6, 7, 12, 13), donate_argnums=(1,))
def _serve_step_paged(params, cache, tok, pos, page_table, cfg: LMConfig,
                      qcfg: QuantConfig, page_size: int, temp, top_k, seeds,
                      n_gen, any_sampled: bool, any_top_k: bool):
    """Paged engine step: eligible attention layers address (N, ps, ·)
    pools through the (B, P) page table; slab-fallback leaves (ring /
    recurrent state) behave exactly as in ``_serve_step``.  Returns
    (next tokens, cache, routing counters or None).  The engine blanks
    the table of every row that decodes for no request, so rows whose
    first page is unmapped stay out of the routing."""
    live = page_table[:, 0] >= 0 if cfg.n_experts else None
    logits, cache, routing = lm_decode_step_routed(
        params, cache, tok, pos, cfg, qcfg, page_table=page_table,
        page_size=page_size, token_mask=live)
    nxt = sample_tokens(logits, temp, top_k, seeds, n_gen,
                        any_sampled, any_top_k)
    return nxt, cache, routing


@partial(SegmentFn, donate_argnums=(0,))
def _insert_row(full, one, slot):
    """Copy a single-request (B=1) cache into batch-cache row ``slot``."""
    return jax.tree.map(
        lambda f, o: jax.lax.dynamic_update_slice_in_dim(
            f, o.astype(f.dtype), slot, axis=1), full, one)


@partial(SegmentFn, donate_argnums=(0,))
def _insert_row_leaves(full_leaves, one_leaves, slot):
    """``_insert_row`` over an explicit leaf subset — the paged engine's
    slab-fallback leaves, whose tree is interleaved with page pools that
    must not be row-sliced."""
    return tuple(jax.lax.dynamic_update_slice_in_dim(
        f, o.astype(f.dtype), slot, axis=1)
        for f, o in zip(full_leaves, one_leaves))


_sample_jit = SegmentFn(sample_tokens, static_argnums=(5, 6),
                        name="serve_sample")


def _bucket(n: int) -> int:
    b = 16
    while b < n:
        b *= 2
    return b


class ServeEngine:
    """Continuous-batching serving engine for one (params, cfg, qcfg).

    ``prefill``: "auto" (fused when the config supports it), "fused"
    (force; raises for unsupported configs) or "stepped" (token-by-token —
    the parity oracle).  ``bucket_prompts=False`` disables prompt-shape
    bucketing even where it is causally safe (exact-length compiles).
    """

    def __init__(self, params, cfg: LMConfig, qcfg: QuantConfig, *,
                 max_batch: int = 4, max_len: int = 256,
                 eos_id: Optional[int] = None, prefill: str = "auto",
                 bucket_prompts: bool = True):
        if prefill not in ("auto", "fused", "stepped"):
            raise ValueError(f"unknown prefill mode {prefill!r}")
        fused_ok = prefill_supported(cfg)
        if prefill == "fused" and not fused_ok:
            raise ValueError(f"config {cfg.name!r} has no fused prefill "
                             "(encoder-decoder / frontend)")
        # Weights are frozen while serving: the dense projections are
        # MX-packed at rest once, and the GEMMs dequantize them on load.
        self.params, self._packing = pack_serving_weights(params, cfg, qcfg)
        self.cfg = cfg
        self.qcfg = qcfg
        self.max_len = max_len
        self.fused = fused_ok if prefill == "auto" else prefill == "fused"
        kinds = {k for pat, _ in block_plan(cfg) for k in pat}
        # Bucketing is causally inert only for purely positional caches:
        # no recurrent state, no ring buffer — and no MoE, where padded
        # tokens would consume expert capacity and perturb real tokens.
        self.pad_safe = (self.fused and bucket_prompts and cfg.window == 0
                         and cfg.n_experts == 0
                         and kinds <= {"attn", "dense_attn"})
        self.sched = Scheduler(max_batch, max_len, eos_id)
        self.cache = self._init_cache()
        # unified runtime journal + device-memory ledger (weights / KV
        # state); cache rebinds every step at fixed shapes, so one
        # accounting at init describes the whole run
        self.events: Journal = Journal()
        self.ledger = MemoryLedger(name="serve")
        self.events.append({"event": "pack_weights", **self._packing})
        self.ledger.account("params", self.params)
        self.ledger.account("cache", self.cache)
        # host phases of each step, on the profiler's clock (serve.*)
        self.spans = Spans("serve")
        self.decode_routing: Optional[np.ndarray] = None
        self.finished: Dict[int, Request] = {}
        self._next_rid = 0
        self._decode_steps = 0
        self._decode_tokens = 0
        self._prefill_tokens = 0
        self._prefill_time = 0.0

    def _init_cache(self):
        return init_cache(self.cfg, self.sched.max_batch, self.max_len)

    # ---- request lifecycle -------------------------------------------------
    def submit(self, prompt, sampling: Optional[SamplingParams] = None) -> int:
        """Queue a prompt (1-D int sequence). Returns the request id."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        sp = sampling or SamplingParams()
        if prompt.size == 0:
            raise ValueError("empty prompt")
        # A prompt that fills the cache exactly leaves no slot for a second
        # token: admitting it would burn a full prefill only to finish
        # "cache_full" at placement.  Reject upfront (a 1-token budget is
        # the one shape that legitimately fits: it finishes "length").
        if prompt.size > self.max_len or (prompt.size == self.max_len
                                          and sp.max_new_tokens > 1):
            raise ValueError(
                f"prompt length {prompt.size} with max_new_tokens "
                f"{sp.max_new_tokens} cannot fit max_len {self.max_len}: "
                "decode needs a cache position per generated token after "
                "the first")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=prompt, sampling=sp,
                      submit_t=time.perf_counter())
        self.sched.submit(req)
        self.events.append({"event": "submit", "rid": rid,
                            "prompt_len": int(prompt.size)})
        return rid

    def _prefill_one(self, req: Request):
        """Warm a (1, S) cache for one request; returns (logits, cache,
        padded_len)."""
        T = req.prompt.size
        toks = req.prompt
        if self.fused:
            Tp = min(_bucket(T), self.max_len) if self.pad_safe else T
            if Tp > T:
                toks = np.concatenate([toks, np.zeros(Tp - T, np.int32)])
            logits, cache = _prefill(
                self.params, jnp.asarray(toks)[None], self.cfg, self.qcfg,
                self.max_len, jnp.asarray([T - 1], jnp.int32))
            return logits, cache, Tp
        cache = init_cache(self.cfg, 1, self.max_len)
        tj = jnp.asarray(toks)[None]
        logits = None
        for t in range(T):
            logits, cache = _decode_step(self.params, cache, tj[:, t:t + 1],
                                         jnp.int32(t), self.cfg, self.qcfg)
        return logits, cache, T

    def _first_token(self, logits, sp: SamplingParams):
        """Dispatch (don't realize) the first-token sample for a prefill."""
        return _sample_jit(
            logits, jnp.asarray([sp.temperature], jnp.float32),
            jnp.asarray([sp.top_k], jnp.int32),
            jnp.asarray([sp.seed], jnp.int32),
            jnp.asarray([0], jnp.int32),
            sp.temperature > 0.0, sp.top_k > 0)

    def _admit(self) -> List[Request]:
        """Admit queued requests into free slots.

        Two-phase: every admission's prefill + row insert + first-token
        sample is *dispatched* first, then results are realized — so the
        host never blocks on one admission's device work before enqueueing
        the next (the old per-admission ``block_until_ready`` serialized
        exactly that).  Latency is taken per request from dispatch to
        first-token realization, matching what the event stream reports.
        """
        finished = []
        staged = []
        with self.spans("admit"):
            admitted = self.sched.admissions()
        for slot, req in admitted:
            with self.spans("prefill", tokens=int(req.prompt.size)):
                req.prefill_t = time.perf_counter()
                logits, one_cache, padded = self._prefill_one(req)
                first = self._first_token(logits, req.sampling)
                self.cache = _insert_row(self.cache, one_cache, slot)
            staged.append((slot, req, first, padded))
        for slot, req, first, padded in staged:
            with self.spans("prefill.wait"):
                tok0 = int(first[0])           # realizes this admission
            with self.spans("place"):
                if self._place(slot, req, tok0, req.prefill_t, padded):
                    finished.append(req)
        return finished

    def _place(self, slot: int, req: Request, tok0: int, since: float,
               padded_len: int, **fields) -> bool:
        """Install a prefilled request in ``slot`` and journal its prefill:
        ``time_s`` from ``since`` to its first token, ``queue_s`` (to its
        first chunk's dispatch) and ``ttft_s`` from its submission.
        Returns True when the request already finished."""
        T = int(req.prompt.size)
        done = self.sched.place(slot, req, tok0, T)
        dt = req.first_token_t - since
        self._prefill_tokens += T
        self._prefill_time += dt
        self.events.append({"event": "prefill", "rid": req.rid,
                            "slot": slot, "prompt_len": T,
                            "padded_len": padded_len, "fused": self.fused,
                            **fields, "time_s": dt,
                            "queue_s": req.prefill_t - req.submit_t,
                            "ttft_s": req.first_token_t - req.submit_t})
        return done

    # ---- stepping ----------------------------------------------------------
    def _pre_decode(self) -> List[Request]:
        """Hook before the batched decode (paged: page growth/preemption).
        Returns requests force-finished here."""
        return []

    def _decode_batch(self, tok, pos, temp, top_k, seeds, n_gen,
                      any_sampled: bool, any_top_k: bool):
        nxt, self.cache = _serve_step(self.params, self.cache, tok, pos,
                                      self.cfg, self.qcfg, temp, top_k,
                                      seeds, n_gen, any_sampled, any_top_k)
        return nxt

    def _fetch(self, nxt) -> np.ndarray:
        """The decode step's sampled tokens on the host (where the host
        blocks on the step)."""
        return np.asarray(nxt)

    def _post_finish(self, finished: List[Request]) -> None:
        """Hook after requests finish (paged: release their pages)."""

    @property
    def has_work(self) -> bool:
        return self.sched.has_work

    def step(self) -> List[Request]:
        """Admit what fits, then advance every live slot one token.
        Returns the requests that finished during this call.

        Each phase is a ``serve.*`` span (``self.spans``); ``decode``
        carries the rows decoded for a request (``live``) and for none
        (``dead``: free, or reserved mid-prefill), and the ``*.wait``
        phases are where the host blocks on the device."""
        with self.spans.step():
            finished = self._admit()
            with self.spans("pages"):
                finished.extend(self._pre_decode())
            n_live = self.sched.n_active
            if n_live:
                # a step's routing reaches the host with its tokens, after
                # its own span: the span carries the step before's
                held = {} if self.decode_routing is None else {
                    "held_tokens": int(self.decode_routing.sum())}
                with self.spans("decode", live=n_live,
                                dead=self.sched.max_batch - n_live, **held):
                    tok, pos, temp, top_k, seeds, n_gen = \
                        self.sched.batch_arrays()
                    nxt = self._decode_batch(
                        tok, pos, temp, top_k, seeds, n_gen,
                        bool((self.sched.temp > 0).any()),
                        bool((self.sched.top_k > 0).any()))
                with self.spans("decode.wait"):
                    nxt = self._fetch(nxt)
                self._decode_steps += 1
                self._decode_tokens += n_live
            with self.spans("finish"):
                if n_live:
                    finished.extend(self.sched.record_step(nxt))
                self._post_finish(finished)
                for req in finished:
                    self.finished[req.rid] = req
                    self.events.append({"event": "request_done",
                                        "rid": req.rid,
                                        "reason": req.finish_reason,
                                        "n_tokens": len(req.tokens),
                                        "latency_s": req.latency_s})
        return finished

    def drain(self) -> List[Request]:
        """Run until queue and slots are empty; returns every finished
        request (rid order)."""
        while self.has_work:
            self.step()
        return [self.finished[rid] for rid in sorted(self.finished)]

    # ---- reporting ---------------------------------------------------------
    def stats(self) -> Dict[str, float]:
        lat = [r.latency_s for r in self.finished.values()
               if r.latency_s is not None]
        decode_time = self.spans.totals["decode"] + \
            self.spans.totals["decode.wait"]
        return {
            "n_finished": float(len(self.finished)),
            "prefill_tokens": float(self._prefill_tokens),
            "prefill_time_s": self._prefill_time,
            "prefill_tok_s": self._prefill_tokens / max(self._prefill_time,
                                                        1e-9),
            "decode_steps": float(self._decode_steps),
            "decode_tokens": float(self._decode_tokens),
            "decode_time_s": decode_time,
            "decode_tok_s": self._decode_tokens / max(decode_time, 1e-9),
            "mean_latency_s": float(np.mean(lat)) if lat else 0.0,
            **self._packing,
        }


# ===========================================================================
# paged engine
# ===========================================================================
class _PrefillJob:
    """A prompt mid-prefill: owns its slot and pages until placement."""

    __slots__ = ("req", "slot", "pages", "n_shared", "chain", "next_start",
                 "n_chunks", "t0")

    def __init__(self, req: Request, slot: int, pages: List[int],
                 n_shared: int, chain: List[bytes], next_start: int):
        self.req = req
        self.slot = slot
        self.pages = pages
        self.n_shared = n_shared
        self.chain = chain
        self.next_start = next_start
        self.n_chunks = 0
        self.t0 = time.perf_counter()


class PagedServeEngine(ServeEngine):
    """Continuous batching over a paged MX KV cache.

    ``n_pages`` × ``page_size`` is the explicit device-memory budget for
    paged attention state; a request maps ``T//ps + 1`` pages (its prompt
    plus decode headroom) instead of a full ``max_len`` slab row, so the
    same budget packs far more mixed-length requests.  Chunk-eligible
    configs (pure global-attention stacks) prefill one ``chunk_size``-token
    chunk per ``step()``, interleaved with live decodes; other configs
    (ring/recurrent/MLA/MoE) prefill whole and are pagified — their
    non-pageable state keeps slab leaves (``kind_paged``).

    Prompt bucketing is disabled: chunking replaces it on the chunked
    path, and the pagify path needs the zero-padded exact-length cache so
    page contents stay bitwise equal to the slab engine's.
    """

    def __init__(self, params, cfg: LMConfig, qcfg: QuantConfig, *,
                 max_batch: int = 4, max_len: int = 256, n_pages: int = 16,
                 page_size: int = 32, chunk_size: Optional[int] = None,
                 eos_id: Optional[int] = None, prefill: str = "auto",
                 prefix_sharing: bool = True):
        if max_len % page_size:
            raise ValueError(f"max_len {max_len} must be a multiple of "
                             f"page_size {page_size} (the page table views "
                             "a whole number of pages per row)")
        self.n_pages = n_pages
        self.page_size = page_size
        self.P = max_len // page_size
        super().__init__(params, cfg, qcfg, max_batch=max_batch,
                         max_len=max_len, eos_id=eos_id, prefill=prefill,
                         bucket_prompts=False)
        self.chunk = chunk_supported(cfg) and self.fused
        if chunk_size is None:
            chunk_size = min(2 * page_size, max_len)
        if chunk_size % page_size:
            raise ValueError(f"chunk_size {chunk_size} must be a multiple "
                             f"of page_size {page_size}")
        self.chunk_size = chunk_size
        self.prefix_sharing = prefix_sharing
        self.alloc = PageAllocator(n_pages, page_size)
        self.page_table = np.full((max_batch, self.P), -1, np.int32)
        self.slot_pages: List[List[int]] = [[] for _ in range(max_batch)]
        self._slot_rid: List[Optional[int]] = [None] * max_batch
        self._admit_seq = np.zeros(max_batch, np.int64)
        self._seq = 0
        self._jobs: Deque[_PrefillJob] = deque()
        self._reserved: Set[int] = set()
        self._ready: List[Tuple[_PrefillJob, Any]] = []
        self._preemptions = 0
        # routing counters still on the device: the prefill chunks' and
        # the last decode step's, read with the decode step's tokens
        self._chunk_routing: List[Any] = []
        self._step_routing = None
        # Flattened-cache metadata: the page pools are a leaf *subset* of
        # the cache tree (slab fallbacks interleave), so the device page
        # helpers map over explicit leaf tuples and the engine reassembles.
        mask_flat = jax.tree_util.tree_flatten(paged_leaf_mask(cfg))[0]
        paths, self._treedef = jax.tree_util.tree_flatten_with_path(
            self.cache)
        self._paged_idx: List[int] = []
        self._slab_idx: List[int] = []
        rules = []
        for i, ((path, _), is_paged) in enumerate(zip(paths, mask_flat)):
            if is_paged:
                self._paged_idx.append(i)
                name = path[-1].key
                rules.append(name if name in ("k", "v") else "raw")
            else:
                self._slab_idx.append(i)
        self._rules = tuple(rules)
        self._rest_fmt = qcfg.a_fwd if qcfg.attn else None
        self._zero_pad = max(self.P, max_batch)
        # split the base class's single cache entry into page pool vs slab
        # fallback, so the ledger shows what the explicit page budget buys
        leaves = self._leaves()
        self.ledger.release("cache")
        self.ledger.account("page_pool",
                            [leaves[i] for i in self._paged_idx])
        self.ledger.account("slab_fallback",
                            [leaves[i] for i in self._slab_idx])

    def _init_cache(self):
        return init_cache_paged(self.cfg, self.sched.max_batch, self.max_len,
                                self.n_pages, self.page_size)

    # ---- leaf plumbing -----------------------------------------------------
    def _leaves(self) -> List[Any]:
        return self._treedef.flatten_up_to(self.cache)

    def _set_pools(self, leaves: List[Any], pools: Tuple[Any, ...]) -> None:
        for i, p in zip(self._paged_idx, pools):
            leaves[i] = p
        self.cache = jax.tree_util.tree_unflatten(self._treedef, leaves)

    def _zero(self, page_ids: List[int]) -> None:
        if not page_ids or not self._paged_idx:
            return
        ids = np.full(self._zero_pad, self.n_pages, np.int32)
        ids[:len(page_ids)] = page_ids
        leaves = self._leaves()
        pools = zero_pages(tuple(leaves[i] for i in self._paged_idx),
                           jnp.asarray(ids))
        self._set_pools(leaves, pools)

    def _row_ids(self, pages: List[int], start_page: int, n: int) -> np.ndarray:
        """Physical ids for logical pages [start_page, start_page+n), with
        the out-of-range sentinel (= n_pages) where unmapped."""
        ids = np.full(n, self.n_pages, np.int32)
        for j in range(n):
            lp = start_page + j
            if lp < len(pages):
                ids[j] = pages[lp]
        return ids

    # ---- admission: jobs, chunks, placement --------------------------------
    def _pages_needed(self, T: int) -> int:
        # Prompt pages plus one decode-headroom page (the first generated
        # token is fed at position T); capped at the per-row view P.
        return min(T // self.page_size + 1, self.P)

    def _start_jobs(self) -> List[Request]:
        finished = []
        while self.sched.queue:
            slot = next((i for i in range(self.sched.max_batch)
                         if self.sched.slots[i] is None
                         and i not in self._reserved), None)
            if slot is None:
                break
            req = self.sched.queue[0]
            T = int(req.prompt.size)
            ps = self.page_size
            need_total = self._pages_needed(T)
            if need_total > self.n_pages:
                # Can never fit, even with the pool to itself.
                self.sched.queue.popleft()
                req.finish_reason = "cache_full"
                req.finish_t = time.perf_counter()
                finished.append(req)
                continue
            chain = prefix_chain(req.prompt, ps) if self.prefix_sharing \
                else []
            # Share at most (T-1)//ps pages: at least one prompt token is
            # always recomputed so the final chunk yields the logits.
            shared = self.alloc.share(chain, (T - 1) // ps)
            fresh = self.alloc.alloc(need_total - len(shared))
            if fresh is None:
                self.alloc.release(shared)
                break                      # wait for live work to free pages
            self.sched.queue.popleft()
            self._zero(fresh)
            pages = shared + fresh
            self.slot_pages[slot] = pages
            self.page_table[slot, :] = -1
            self.page_table[slot, :len(pages)] = pages
            self._reserved.add(slot)
            job = _PrefillJob(req, slot, pages, len(shared), chain,
                              next_start=len(shared) * ps)
            self._jobs.append(job)
        return finished

    def _advance_job(self) -> None:
        """Run one prefill chunk of the oldest in-flight job (whole-prompt
        prefill + pagify for chunk-ineligible configs).  One chunk per
        ``step()`` keeps prompt work interleaved with live decodes."""
        if not self._jobs:
            return
        job = self._jobs[0]
        req, T, ps = job.req, int(job.req.prompt.size), self.page_size
        qc = self.qcfg
        if not job.n_chunks:
            req.prefill_t = time.perf_counter()
        if not self.chunk:
            logits, one_cache, _ = self._prefill_one(req)
            one_leaves = jax.tree_util.tree_leaves(one_cache)
            leaves = self._leaves()
            if self._slab_idx:
                slabs = _insert_row_leaves(
                    tuple(leaves[i] for i in self._slab_idx),
                    tuple(one_leaves[i] for i in self._slab_idx), job.slot)
                for i, s in zip(self._slab_idx, slabs):
                    leaves[i] = s
            if self._paged_idx:
                ids = self._row_ids(job.pages, 0, self.P)
                pools = write_chunk_pages(
                    tuple(leaves[i] for i in self._paged_idx),
                    tuple(one_leaves[i] for i in self._paged_idx),
                    jnp.asarray(ids), np.int32(T // ps), self._rules,
                    self._rest_fmt, qc.block, qc.scale_mode)
                for i, p in zip(self._paged_idx, pools):
                    leaves[i] = p
            self.cache = jax.tree_util.tree_unflatten(self._treedef, leaves)
            job.n_chunks = 1
            self._ready.append((job, self._first_token(logits, req.sampling)))
            self._jobs.popleft()
            return
        start = job.next_start
        C = self.chunk_size
        real = min(T - start, C)
        toks = np.zeros(C, np.int32)
        toks[:real] = req.prompt[start:start + real]
        kv_mask = jnp.asarray((np.arange(C) < real)[None])
        leaves = self._leaves()
        pools = tuple(leaves[i] for i in self._paged_idx)
        prior_ids = self._row_ids(job.pages, 0, start // ps)
        prior = jax.tree_util.tree_unflatten(
            self._treedef, list(gather_prior(pools, jnp.asarray(prior_ids),
                                             self._rules)))
        logits, chunk_kv, routing = _prefill_chunk(
            self.params, jnp.asarray(toks)[None], prior, start, self.cfg, qc,
            jnp.asarray([real - 1], jnp.int32), kv_mask)
        if routing is not None:
            self._chunk_routing.append(routing)
        ids = self._row_ids(job.pages, start // ps, C // ps)
        n_sealed = max(0, min(T // ps - start // ps, C // ps))
        pools = write_chunk_pages(
            pools, tuple(jax.tree_util.tree_leaves(chunk_kv)),
            jnp.asarray(ids), np.int32(n_sealed), self._rules,
            self._rest_fmt, qc.block, qc.scale_mode)
        self._set_pools(leaves, pools)
        job.n_chunks += 1
        job.next_start = start + C
        if job.next_start >= T:
            self._ready.append((job, self._first_token(logits, req.sampling)))
            self._jobs.popleft()

    def _admit(self) -> List[Request]:
        with self.spans("admit"):
            finished = self._start_jobs()
        # Refill an under-occupied batch fast: with idle rows the decode
        # step is paying fixed cost anyway, so run one prefill chunk per
        # idle row (min 1) instead of strictly one per step; a full batch
        # drops back to one chunk per step to protect decode latency.
        budget = max(1, self.sched.max_batch - self.sched.n_active)
        for _ in range(budget):
            if not self._jobs:
                break
            job = self._jobs[0]
            T = int(job.req.prompt.size)
            real = min(T - job.next_start, self.chunk_size) \
                if self.chunk else T
            with self.spans("prefill", tokens=real):
                self._advance_job()
        finished.extend(self._place_ready())
        return finished

    def _place_ready(self) -> List[Request]:
        """Install jobs whose final chunk just ran.  Placement happens in
        the same ``step()``: a completed-but-unplaced job's slot is still
        dead, and the next decode's dummy write would clobber its freshly
        written slab leaves (ring/recurrent state can't hide behind the
        page-table drop sentinel the way pool leaves do)."""
        finished = []
        while self._ready:
            job, first = self._ready.pop(0)
            req = job.req
            T = int(req.prompt.size)
            with self.spans("prefill.wait"):
                tok0 = int(first[0])
            with self.spans("place"):
                self._reserved.discard(job.slot)
                self._slot_rid[job.slot] = req.rid
                self._admit_seq[job.slot] = self._seq
                self._seq += 1
                if self.prefix_sharing:
                    full = T // self.page_size
                    self.alloc.register(job.chain[:full], job.pages[:full])
                if self._place(job.slot, req, tok0, job.t0, T,
                               chunks=job.n_chunks,
                               shared_pages=job.n_shared):
                    finished.append(req)
        return finished

    # ---- page lifecycle ----------------------------------------------------
    def _release_slot(self, slot: int) -> None:
        if self.slot_pages[slot]:
            self.alloc.release(self.slot_pages[slot])
        self.slot_pages[slot] = []
        self.page_table[slot, :] = -1
        self._slot_rid[slot] = None

    def _post_finish(self, finished: List[Request]) -> None:
        rids = {req.rid for req in finished}
        for slot in range(self.sched.max_batch):
            if self._slot_rid[slot] in rids:
                self._release_slot(slot)

    def _preempt(self, exclude: int) -> bool:
        """Evict the most recently admitted live request (LIFO — it has
        the least sunk decode work) and requeue it at the queue front for
        a deterministic replay (same seed/n_gen stream → same tokens)."""
        cands = [s for s in range(self.sched.max_batch)
                 if self.sched.slots[s] is not None and s != exclude]
        if not cands:
            return False
        victim = max(cands, key=lambda s: self._admit_seq[s])
        req = self.sched.slots[victim]
        self.sched.slots[victim] = None
        self._scrub_slot(victim)
        self._release_slot(victim)
        req.tokens.clear()
        req.prefill_t = None
        req.first_token_t = None
        self.sched.queue.appendleft(req)
        self._preemptions += 1
        self.events.append({"event": "preempt", "rid": req.rid,
                            "slot": victim})
        return True

    def _scrub_slot(self, slot: int) -> None:
        s = self.sched
        s.pos[slot] = 0
        s.cur_tok[slot] = 0
        s.temp[slot] = 0.0
        s.top_k[slot] = 0
        s.seeds[slot] = 0
        s.n_gen[slot] = 0

    def _force_finish(self, slot: int, reason: str) -> Request:
        req = self.sched.slots[slot]
        req.finish_reason = reason
        req.finish_t = time.perf_counter()
        self.sched.slots[slot] = None
        self._scrub_slot(slot)
        self._release_slot(slot)
        return req

    def _pre_decode(self) -> List[Request]:
        """Grow each live row's page map to cover the position it writes
        this step; resolve pressure by preemption, or finish the row
        "cache_full" when it is alone in the pool."""
        finished = []
        fresh_ids: List[int] = []
        for slot in range(self.sched.max_batch):
            req = self.sched.slots[slot]
            if req is None:
                continue
            need = int(self.sched.pos[slot]) // self.page_size + 1
            while len(self.slot_pages[slot]) < need:
                got = self.alloc.alloc(1)
                if got is None:
                    if not self._preempt(exclude=slot):
                        finished.append(self._force_finish(slot,
                                                           "cache_full"))
                        break
                    continue
                idx = len(self.slot_pages[slot])
                self.slot_pages[slot].append(got[0])
                self.page_table[slot, idx] = got[0]
                fresh_ids.append(got[0])
        self._zero(fresh_ids)
        return finished

    # ---- decode ------------------------------------------------------------
    def _decode_batch(self, tok, pos, temp, top_k, seeds, n_gen,
                      any_sampled: bool, any_top_k: bool):
        # The fixed-shape step decodes every row, live or not.  A dead
        # slot's slab writes land in a row nobody reads, but a reserved
        # slot's table already maps real pages mid-prefill — so the decode
        # view blanks every non-live row (dummy writes hit the drop
        # sentinel instead of clobbering page 0 of an in-flight prompt).
        live = np.fromiter((r is not None for r in self.sched.slots),
                           bool, self.sched.max_batch)
        pt = np.where(live[:, None], self.page_table, -1).astype(np.int32)
        nxt, self.cache, self._step_routing = _serve_step_paged(
            self.params, self.cache, tok, pos, jnp.asarray(pt), self.cfg,
            self.qcfg, self.page_size, temp, top_k, seeds, n_gen,
            any_sampled, any_top_k)
        return nxt

    def _fetch(self, nxt) -> np.ndarray:
        """The sampled tokens, and with them the routing counters of this
        step and of the prefill chunks dispatched before it (done by now,
        so reading them adds no wait)."""
        nxt, step, chunks = jax.device_get(
            (nxt, self._step_routing, self._chunk_routing))
        self._step_routing, self._chunk_routing = None, []
        self._count_routing(chunks + ([step] if step is not None else []))
        self.decode_routing = None if step is None else step["held_tokens"]
        return np.asarray(nxt)

    def _count_routing(self, routings) -> None:
        for r in routings:
            self.spans.counters["moe.held_tokens"] += float(
                r["held_tokens"].sum())
            self.spans.counters["moe.dropped"] += float(r["dropped"])

    @property
    def has_work(self) -> bool:
        return (self.sched.has_work or bool(self._jobs)
                or bool(self._ready))

    # ---- reporting ---------------------------------------------------------
    def stats(self) -> Dict[str, float]:
        out = super().stats()
        if self.cfg.n_experts:
            chunks = jax.device_get(self._chunk_routing)
            self._chunk_routing = []
            self._count_routing(chunks)
            out.update({k: self.spans.counters[k]
                        for k in ("moe.held_tokens", "moe.dropped")})
        out.update({
            "n_pages": float(self.n_pages),
            "page_size": float(self.page_size),
            "pages_in_use": float(self.alloc.pages_in_use),
            "pages_free": float(self.alloc.n_free),
            "prefix_hits": float(self.alloc.prefix_hits),
            "evictions": float(self.alloc.evictions),
            "preemptions": float(self._preemptions),
        })
        return out
