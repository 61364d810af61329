"""Paged MX KV cache: page-table allocator + device page helpers.

The serving cache moves from per-slot slabs (every request owns a
(max_len, ·) stripe regardless of its length) to a global pool of
fixed-size pages, vLLM-style, with the page size a multiple of ``MX_BLOCK``
so pages align with the 32-wide MX block grid: at-rest page quantization
and the decode kernels' block scales then share the same boundaries, and
the paging transform stays bitwise-invisible (Q(Q(x)) == Q(x) per aligned
block — the quantizer idempotence pinned by tests/test_mx_formats.py).

Host side (:class:`PageAllocator`, pure numpy/python — no device work):

  * a free list + per-page refcounts; a request owns one reference per
    page it maps;
  * prefix sharing keyed on a rolling prompt-prefix hash chain: full
    prompt pages are registered per chain hash, and a new request walks
    its own chain from the start, sharing every hit (ref+1 — the pages
    are immutable, so "copy-on-write" degenerates to share-immutable /
    write-private: decode always writes pages past the shared prefix);
  * admission/eviction under the explicit ``n_pages`` device budget:
    cached prefix entries whose pages are unreferenced are evicted LRU
    (cascading to descendant entries so a chain never dangles); pages
    referenced by a live request are never evicted.

Device side: jitted helpers over the *pool leaves* of a paged cache tree
(``models.init_cache_paged``) — zeroing freshly allocated pages, gathering
a prefix view for chunked prefill, and writing a prefill chunk into its
pages with at-rest MX quantization of sealed (fully-written) pages.
"""
from __future__ import annotations

import dataclasses
import hashlib
from collections import OrderedDict
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mx import MX_BLOCK, quantize_mx

__all__ = ["PageAllocator", "prefix_chain", "zero_pages", "gather_prior",
           "write_chunk_pages"]


# ---------------------------------------------------------------------------
# prompt-prefix hash chain
# ---------------------------------------------------------------------------
def prefix_chain(prompt: np.ndarray, page_size: int) -> List[bytes]:
    """Rolling hash per *full* prompt page: ``h_i = H(h_{i-1} || tokens_i)``
    — equal chains imply equal token prefixes, so a chain hash is a safe
    content key for the page holding positions [i*ps, (i+1)*ps)."""
    out: List[bytes] = []
    h = b""
    n_full = len(prompt) // page_size
    for i in range(n_full):
        blk = np.ascontiguousarray(prompt[i * page_size:(i + 1) * page_size],
                                   dtype=np.int32)
        h = hashlib.blake2b(h + blk.tobytes(), digest_size=16).digest()
        out.append(h)
    return out


class PageAllocator:
    """Host-side page bookkeeping under a fixed ``n_pages`` budget."""

    def __init__(self, n_pages: int, page_size: int):
        if page_size % MX_BLOCK:
            raise ValueError(f"page_size {page_size} must be a multiple of "
                             f"MX_BLOCK ({MX_BLOCK})")
        self.n_pages = n_pages
        self.page_size = page_size
        self.free: List[int] = list(range(n_pages - 1, -1, -1))
        self.ref = np.zeros(n_pages, np.int32)
        # prefix cache: chain hash -> page, LRU-ordered; reverse map and
        # parent/children links for cascading eviction.
        self.prefix: "OrderedDict[bytes, int]" = OrderedDict()
        self.cached_page: Dict[int, bytes] = {}
        self.parent: Dict[bytes, Optional[bytes]] = {}
        self.children: Dict[bytes, set] = {}
        self.prefix_hits = 0
        self.evictions = 0

    # ---- capacity ----------------------------------------------------------
    @property
    def n_free(self) -> int:
        return len(self.free)

    @property
    def n_evictable(self) -> int:
        return sum(1 for p in self.cached_page if self.ref[p] == 0)

    def available(self) -> int:
        """Pages obtainable right now: free + evictable cached."""
        return self.n_free + self.n_evictable

    @property
    def pages_in_use(self) -> int:
        return int((self.ref > 0).sum())

    # ---- prefix cache ------------------------------------------------------
    def share(self, chain: Sequence[bytes], limit: int) -> List[int]:
        """Walk the chain from the start, taking a reference on every
        cached page (at most ``limit``); stops at the first miss."""
        out: List[int] = []
        for h in chain[:limit]:
            page = self.prefix.get(h)
            if page is None:
                break
            self.prefix.move_to_end(h)           # LRU touch
            self.ref[page] += 1
            self.prefix_hits += 1
            out.append(page)
        return out

    def register(self, chain: Sequence[bytes], pages: Sequence[int]) -> None:
        """Publish a request's full prompt pages under their chain hashes
        (idempotent for already-cached prefixes)."""
        parent: Optional[bytes] = None
        for h, page in zip(chain, pages):
            if h not in self.prefix:
                self.prefix[h] = page
                self.cached_page[page] = h
                self.parent[h] = parent
                self.children.setdefault(h, set())
                if parent is not None:
                    self.children.setdefault(parent, set()).add(h)
            self.prefix.move_to_end(h)
            parent = h

    def _evict_entry(self, h: bytes) -> int:
        """Drop a cache entry and (recursively) its descendants; frees
        every evicted page whose refcount is zero.  Returns #pages freed.
        Never touches a live (ref > 0) page's contents — a still-referenced
        page merely loses its cache entry and is freed when released."""
        freed = 0
        for child in list(self.children.get(h, ())):
            freed += self._evict_entry(child)
        page = self.prefix.pop(h, None)
        if page is None:
            return freed
        self.evictions += 1
        self.cached_page.pop(page, None)
        par = self.parent.pop(h, None)
        if par is not None and par in self.children:
            self.children[par].discard(h)
        self.children.pop(h, None)
        if self.ref[page] == 0:
            self.free.append(page)
            freed += 1
        return freed

    # ---- alloc / release ---------------------------------------------------
    def alloc(self, n: int) -> Optional[List[int]]:
        """Take ``n`` fresh pages (refcount 1), evicting LRU cached
        prefixes as needed.  Returns None (and changes nothing visible to
        live requests) when the budget cannot cover the ask."""
        if self.available() < n:
            return None
        while len(self.free) < n:
            # Oldest entry whose page is evictable; cascade handles chains.
            victim = next((h for h, p in self.prefix.items()
                           if self.ref[p] == 0), None)
            if victim is None:
                return None
            self._evict_entry(victim)
        out = [self.free.pop() for _ in range(n)]
        for p in out:
            self.ref[p] = 1
        return out

    def release(self, pages: Sequence[int]) -> None:
        """Drop one reference per page; unreferenced uncached pages return
        to the free list (cached ones stay resident as prefix entries)."""
        for p in pages:
            assert self.ref[p] > 0, f"double free of page {p}"
            self.ref[p] -= 1
            if self.ref[p] == 0 and p not in self.cached_page:
                self.free.append(p)

    # ---- invariants (tests) ------------------------------------------------
    def check(self) -> None:
        free = set(self.free)
        assert len(free) == len(self.free), "free list duplicates"
        for p in free:
            assert self.ref[p] == 0, f"free page {p} has refs"
            assert p not in self.cached_page, f"free page {p} still cached"
        for h, p in self.prefix.items():
            assert self.cached_page.get(p) == h, "prefix/reverse-map drift"
            par = self.parent.get(h)
            if par is not None:
                assert par in self.prefix, f"dangling parent for {h!r}"
        accounted = len(free) + len(
            {p for p in range(self.n_pages)
             if self.ref[p] > 0 or p in self.cached_page})
        assert accounted == self.n_pages, "page leak"


# ---------------------------------------------------------------------------
# device helpers (operate on the tuple of page-pool leaves)
# ---------------------------------------------------------------------------
@partial(jax.jit, donate_argnums=(0,))
def zero_pages(pools: Tuple[jax.Array, ...], ids: jax.Array):
    """Zero physical pages ``ids`` ((m,) int32; pad with >= N to no-op) in
    every pool leaf — freshly (re)allocated pages must not leak a previous
    tenant's values into at-rest MX block scales (or anything else)."""
    def z(p):
        zeros = jnp.zeros((p.shape[0], ids.shape[0]) + p.shape[2:], p.dtype)
        return p.at[:, ids].set(zeros, mode="drop")
    return tuple(z(p) for p in pools)


@partial(jax.jit, static_argnames=("rules",))
def gather_prior(pools: Tuple[jax.Array, ...], ids: jax.Array,
                 rules: Tuple[str, ...]):
    """Assemble the contiguous (n_rep, 1, n*ps, ...) prefix view of the
    first ``n`` logical pages (``ids``: (n,) physical ids, all valid) —
    what a prefill chunk attends to as its prior K/V.  ``rules`` as in
    :func:`write_chunk_pages`: "k"/"v" pools are head-major and come back
    position-major, like the chunk K/V they extend."""
    def g(p, rule):
        n_rep, N = p.shape[:2]
        gp = p[:, jnp.clip(ids, 0, N - 1)]           # (n_rep, n, ...)
        if rule in ("k", "v"):
            gp = jnp.swapaxes(gp, 2, 3)              # (n_rep, n, ps, H, d)
        return gp.reshape((n_rep, 1, ids.shape[0] * gp.shape[2])
                          + gp.shape[3:])
    return tuple(g(p, r) for p, r in zip(pools, rules))


@partial(jax.jit, donate_argnums=(0,),
         static_argnames=("rules", "fmt", "block", "scale_mode"))
def write_chunk_pages(pools: Tuple[jax.Array, ...],
                      chunks: Tuple[jax.Array, ...], ids: jax.Array,
                      n_sealed, rules: Tuple[str, ...], fmt,
                      block: int = MX_BLOCK, scale_mode: str = "floor"):
    """Scatter one prefill chunk (leaves (n_rep, 1, C, ...), C = len(ids) *
    ps) into physical pages ``ids`` (pad with >= N to drop), MX-quantizing
    sealed pages at rest.

    ``rules`` names each leaf's layout and at-rest treatment.  "k" and "v"
    are head-major (n_rep, N, H, ps, d) K/V pools; "k" quantizes along the
    head dim (per-position blocks — always safe), "v" along the in-page
    position axis but only for the first ``n_sealed`` fully-real pages (a
    partial page's block max would shift as later tokens arrive, breaking
    Q∘Q idempotence).  "raw" leaves are position-major (n_rep, N, ps, ·)
    and stored bf16 (MLA latents).  Because the decode
    oracle quantizes with the same axes and page-aligned blocks, at-rest
    quantization is bitwise-invisible to attention output."""
    n_pg = ids.shape[0]

    def w(pool, ck, rule):
        n_rep, ps = pool.shape[0], ck.shape[2] // n_pg
        pages = ck.reshape((n_rep, n_pg, ps) + ck.shape[3:])
        if fmt is not None and rule in ("k", "v"):
            axis = -1 if rule == "k" else 2
            q = quantize_mx(pages.astype(jnp.float32), fmt, axis=axis,
                            block=block, scale_mode=scale_mode)
            sealed = jnp.arange(n_pg) < n_sealed
            sh = (1, n_pg) + (1,) * (pages.ndim - 2)
            pages = jnp.where(sealed.reshape(sh), q,
                              pages.astype(jnp.float32))
        if rule in ("k", "v"):
            pages = jnp.swapaxes(pages, 2, 3)        # head-major, as the pool
        return pool.at[:, ids].set(pages.astype(pool.dtype), mode="drop")

    return tuple(w(p, c, r) for p, c, r in zip(pools, chunks, rules))
