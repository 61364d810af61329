"""Paged KV cache: engine parity vs the slab oracle + allocator invariants.

The central claim of the paged serving stack is that paging is *invisible*:
for greedy decode the :class:`PagedServeEngine` (page pools + page-table
gather + at-rest MX page quantization + chunked prefill + prefix sharing +
preemption) produces **bitwise identical** token streams to the fixed-slab
:class:`ServeEngine` run with the same (params, cfg, qcfg).  Everything
here pins that claim and the host-side allocator's bookkeeping:

  * paged-vs-slab greedy parity across {bf16, mxfp8_e4m3} x {chunked
    global attention, ring/recurrent slab fallback, MLA pagify};
  * prefix sharing (copy-on-write prefix cache) changes nothing about the
    outputs while actually sharing pages across waves;
  * preemption under page pressure replays deterministically;
  * eviction only ever touches unreferenced cached pages; the allocator's
    accounting survives the full lifecycle (``PageAllocator.check()``);
  * requests that can never fit fail fast, lone requests that outgrow the
    pool finish "cache_full" at the exact page-capacity boundary;
  * the paged decode kernel path is bit-identical to gather+slab;
  * weights MX-packed at rest serve the tokens the bf16 weights serve.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import preset, use_fused_gemms
from repro.core.mx import MXWeight
from repro.core.formats import E4M3
from repro.kernels import (gather_pages, mx_attention_decode,
                           mx_attention_decode_paged,
                           mx_attention_decode_paged_ref)
from repro.models import lm_init
from repro.serve import (PageAllocator, PagedServeEngine, SamplingParams,
                         ServeEngine, prefix_chain)

_SETUP = {}


def _setup(arch):
    if arch not in _SETUP:
        cfg = get_config(arch, "smoke")
        params = lm_init(jax.random.PRNGKey(0), cfg)
        _SETUP[arch] = (cfg, params)
    return _SETUP[arch]


def _submit_all(eng, prompts, max_new=8, sample_every=0):
    rids = []
    for i, p in enumerate(prompts):
        sampled = sample_every and (i % sample_every == sample_every - 1)
        sp = SamplingParams(temperature=0.8 if sampled else 0.0,
                            top_k=20 if sampled else 0,
                            max_new_tokens=max_new, seed=300 + i)
        rids.append(eng.submit(p, sp))
    return rids


def _results(eng):
    return {r.rid: (tuple(r.tokens), r.finish_reason) for r in eng.drain()}


# ---------------------------------------------------------------------------
# parity matrix: paged engine == slab engine, bitwise
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("prec", ("bf16", "mxfp8_e4m3"))
@pytest.mark.parametrize("arch", ["qwen2-7b", "recurrentgemma-9b",
                                  "deepseek-v2-236b"])
def test_paged_vs_slab_greedy_parity(arch, prec):
    """qwen2: chunked prefill + fully paged pools; recurrentgemma: ring +
    recurrent state = pure slab fallback (0 paged leaves); deepseek MLA:
    whole-prompt prefill pagified into raw-latent pools.  All three must
    match the slab engine token-for-token, greedy and sampled rows alike
    (a sampled row's stream is a pure function of bitwise-equal logits)."""
    cfg, params = _setup(arch)
    qcfg = preset(prec)
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, cfg.vocab, size=n) for n in (5, 40, 70, 33)]

    slab = ServeEngine(params, cfg, qcfg, max_batch=3, max_len=128,
                       bucket_prompts=False)
    paged = PagedServeEngine(params, cfg, qcfg, max_batch=3, max_len=128,
                             n_pages=16, page_size=32)
    _submit_all(slab, prompts, sample_every=4)
    _submit_all(paged, prompts, sample_every=4)
    assert _results(paged) == _results(slab)
    paged.alloc.check()
    assert paged.alloc.pages_in_use == 0


def test_paged_parity_across_batch_widths_and_page_boundaries():
    """Prompt lengths straddling page/chunk boundaries (T = ps-1, ps, ps+1,
    2*chunk, multi-chunk) at two batch widths — placement order and chunk
    interleave differ, results must not."""
    cfg, params = _setup("qwen2-7b")
    qcfg = preset("mxfp8_e4m3")
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, cfg.vocab, size=n)
               for n in (31, 32, 33, 64, 96, 7)]

    def run(max_batch):
        eng = PagedServeEngine(params, cfg, qcfg, max_batch=max_batch,
                               max_len=128, n_pages=24, page_size=32)
        _submit_all(eng, prompts, max_new=6)
        out = _results(eng)
        eng.alloc.check()
        return out

    slab = ServeEngine(params, cfg, qcfg, max_batch=2, max_len=128,
                       bucket_prompts=False)
    _submit_all(slab, prompts, max_new=6)
    ref = _results(slab)
    assert run(2) == ref
    assert run(4) == ref


# ---------------------------------------------------------------------------
# prefix sharing
# ---------------------------------------------------------------------------
def test_prefix_sharing_shares_pages_without_changing_outputs():
    """Two waves with a common 64-token prefix: the second wave must hit
    the prefix cache (pages shared by content) and still match the slab
    engine bitwise — shared pages are immutable, decode writes only
    private pages past the prefix (share-immutable / write-private)."""
    cfg, params = _setup("qwen2-7b")
    qcfg = preset("mxfp8_e4m3")
    rng = np.random.RandomState(21)
    prefix = rng.randint(1, cfg.vocab, size=64)
    prompts = [np.concatenate([prefix, rng.randint(1, cfg.vocab, size=n)])
               for n in (9, 17, 5, 26)]

    slab = ServeEngine(params, cfg, qcfg, max_batch=2, max_len=128,
                       bucket_prompts=False)
    paged = PagedServeEngine(params, cfg, qcfg, max_batch=2, max_len=128,
                             n_pages=20, page_size=32)
    # Wave 1 populates the prefix cache; wave 2 must share its pages.
    _submit_all(slab, prompts[:2], max_new=6)
    ref = _results(slab)
    _submit_all(slab, prompts[2:], max_new=6)
    ref.update(_results(slab))

    _submit_all(paged, prompts[:2], max_new=6)
    out = _results(paged)
    _submit_all(paged, prompts[2:], max_new=6)
    out.update(_results(paged))

    assert out == ref
    assert paged.alloc.prefix_hits >= 2     # wave 2 reused cached pages
    shared = [e["shared_pages"] for e in paged.events
              if e["event"] == "prefill"]
    assert max(shared) >= 2                 # 64-token prefix = 2 pages
    paged.alloc.check()


def test_prefix_chain_is_positional_and_content_keyed():
    ps = 32
    rng = np.random.RandomState(0)
    a = rng.randint(1, 1000, size=70).astype(np.int32)
    assert len(prefix_chain(a, ps)) == 2          # only full pages hash
    b = a.copy()
    b[40] += 1                                    # differ in page 1 only
    ca, cb = prefix_chain(a, ps), prefix_chain(b, ps)
    assert ca[0] == cb[0] and ca[1] != cb[1]
    # Same tokens at a different page offset must not collide (rolling
    # chain: h_i depends on every preceding page).
    c = np.concatenate([[7], a[:63]]).astype(np.int32)
    assert prefix_chain(c, ps)[0] != ca[0]


# ---------------------------------------------------------------------------
# preemption + pool exhaustion
# ---------------------------------------------------------------------------
def test_preemption_replays_deterministically():
    """A pool too small for all three requests' full decode forces a LIFO
    preemption; the victim replays from scratch with the same RNG stream,
    so every request still matches the (amply provisioned) slab engine."""
    cfg, params = _setup("qwen2-7b")
    qcfg = preset("mxfp8_e4m3")
    rng = np.random.RandomState(31)
    prompts = [rng.randint(1, cfg.vocab, size=40) for _ in range(3)]

    slab = ServeEngine(params, cfg, qcfg, max_batch=3, max_len=128,
                       bucket_prompts=False)
    paged = PagedServeEngine(params, cfg, qcfg, max_batch=3, max_len=128,
                             n_pages=6, page_size=32)
    _submit_all(slab, prompts, max_new=40)
    _submit_all(paged, prompts, max_new=40)
    assert _results(paged) == _results(slab)
    assert paged._preemptions >= 1
    assert all(r.finish_reason == "length" for r in paged.finished.values())
    # After drain every page is reclaimable: free outright, or resident
    # only as an unreferenced cached prefix (evictable on demand).
    assert paged.alloc.n_free + paged.alloc.n_evictable == 6
    paged.alloc.check()


def test_oversize_request_fails_fast():
    """A prompt needing more pages than the whole pool finishes
    "cache_full" immediately — no prefill work is burned on it."""
    cfg, params = _setup("qwen2-7b")
    qcfg = preset("bf16")
    eng = PagedServeEngine(params, cfg, qcfg, max_batch=2, max_len=128,
                           n_pages=2, page_size=32)
    eng.submit(np.arange(1, 101, dtype=np.int32),
               SamplingParams(max_new_tokens=8))
    (r,) = eng.drain()
    assert r.finish_reason == "cache_full" and r.tokens == []
    assert not [e for e in eng.events if e["event"] == "prefill"]
    eng.alloc.check()


def test_lone_request_exhausts_pool_at_page_capacity():
    """With nobody to preempt, decode growth stops exactly when the pool's
    token capacity (n_pages * ps) is filled: T=40 into 2 pages = 64
    positions -> 64 - 40 + 1 generated tokens."""
    cfg, params = _setup("qwen2-7b")
    qcfg = preset("bf16")
    eng = PagedServeEngine(params, cfg, qcfg, max_batch=2, max_len=128,
                           n_pages=2, page_size=32)
    eng.submit(np.arange(1, 41, dtype=np.int32),
               SamplingParams(max_new_tokens=40))
    (r,) = eng.drain()
    assert r.finish_reason == "cache_full"
    assert len(r.tokens) == 64 - 40 + 1
    assert eng.alloc.n_free + eng.alloc.n_evictable == 2
    eng.alloc.check()


# ---------------------------------------------------------------------------
# allocator unit behavior (pure host bookkeeping)
# ---------------------------------------------------------------------------
def test_allocator_eviction_never_touches_live_pages():
    al = PageAllocator(n_pages=4, page_size=32)
    chain = prefix_chain(np.arange(128, dtype=np.int32), 32)  # 4 hashes
    pages = al.alloc(4)
    al.register(chain, pages)
    # Live pages: a second request shares the first two.
    shared = al.share(chain, 2)
    assert shared == pages[:2] and al.prefix_hits == 2
    al.release(pages)               # first owner leaves; 2 still referenced
    assert al.n_free == 0           # cached pages stay resident
    assert al.available() == 2      # only the unreferenced ones evictable
    got = al.alloc(2)               # forces eviction of the tail entries
    assert got is not None and set(got).isdisjoint(shared)
    assert al.evictions >= 2
    # The shared pages survived eviction with their cache entries... or at
    # least their contents: they are still referenced either way.
    assert all(al.ref[p] == 1 for p in shared)
    assert al.alloc(1) is None      # pool genuinely exhausted now
    al.release(shared)
    al.release(got)
    al.check()


def test_allocator_cascade_eviction_keeps_chains_rooted():
    """Evicting a chain entry drops its descendants too: a cached child
    whose parent is gone would be unreachable by any future share() walk
    (walks always start at the chain root)."""
    al = PageAllocator(n_pages=3, page_size=32)
    chain = prefix_chain(np.arange(96, dtype=np.int32), 32)
    pages = al.alloc(3)
    al.register(chain, pages)
    al.release(pages)
    assert al.alloc(1) is not None  # evicts the root -> whole chain goes
    for h, p in al.prefix.items():
        par = al.parent.get(h)
        assert par is None or par in al.prefix
    al.check()


def test_allocator_rejects_misaligned_page_size():
    with pytest.raises(ValueError):
        PageAllocator(n_pages=4, page_size=48)   # not a MX_BLOCK multiple
    with pytest.raises(ValueError):
        PagedServeEngine(None, None, None, max_len=100, page_size=32)


def test_allocator_double_free_asserts():
    al = PageAllocator(n_pages=2, page_size=32)
    (p,) = al.alloc(1)
    al.release([p])
    with pytest.raises(AssertionError):
        al.release([p])


# ---------------------------------------------------------------------------
# paged decode kernel == gather + slab decode (bit-exact)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fmt", [None, E4M3], ids=["bf16", "e4m3"])
def test_paged_decode_kernel_bit_identical_to_gather_plus_slab(fmt):
    """The paging transform is only a gather: paged kernel output must be
    bitwise equal both to the paged oracle and to the slab decode run on
    the explicitly gathered contiguous view."""
    rng = np.random.RandomState(9)
    B, H, G, d, ps, P, N = 2, 2, 2, 32, 32, 4, 8
    q = jnp.asarray(rng.randn(B * H, G, d).astype(np.float32))
    # head-major (N, H, ps, d) pools
    k_pool = jnp.asarray(rng.randn(N, ps, H, d).astype(np.float32)
                         .transpose(0, 2, 1, 3))
    v_pool = jnp.asarray(rng.randn(N, ps, H, d).astype(np.float32)
                         .transpose(0, 2, 1, 3))
    pt = jnp.asarray([[5, 2, -1, -1], [0, 7, 3, -1]], jnp.int32)
    pos = jnp.asarray([[40], [70]])
    valid = (jnp.arange(P * ps)[None, :] <= pos) & (
        jnp.repeat(pt >= 0, ps, axis=1))
    o_k = mx_attention_decode_paged(q, k_pool, v_pool, pt, valid, fmt)
    o_r = mx_attention_decode_paged_ref(q, k_pool, v_pool, pt, valid, fmt)
    np.testing.assert_array_equal(np.asarray(o_k), np.asarray(o_r))
    o_s = mx_attention_decode(q, gather_pages(k_pool, pt, H),
                              gather_pages(v_pool, pt, H),
                              jnp.repeat(valid, H, axis=0), fmt)
    np.testing.assert_array_equal(np.asarray(o_k), np.asarray(o_s))


# ---------------------------------------------------------------------------
# weights MX-packed at rest (the engine packs; the GEMMs dequantize)
# ---------------------------------------------------------------------------
def _tied_starcoder():
    cfg = dataclasses.replace(get_config("starcoder2-3b", "smoke"),
                              tie_embeddings=True)
    return cfg, lm_init(jax.random.PRNGKey(0), cfg)


@pytest.mark.parametrize("fused", [False, True],
                         ids=["emulation", "kernels"])
def test_packed_weights_serve_the_bf16_weights_greedy_tokens(fused):
    """Under e4m3_bf16act the engine serves every dense projection and the
    tied head packed (share 1.0) and decodes the greedy tokens that the
    same engine decodes from the caller's bf16 weights (quantized on
    load), with the fused kernels forced on (interpret mode) or off.  The
    caller's params come out unchanged, leaf for leaf."""
    cfg, params = _tied_starcoder()
    before = jax.tree.map(np.array, params)
    qcfg = preset("e4m3_bf16act")
    rng = np.random.RandomState(21)
    prompts = [rng.randint(1, cfg.vocab, size=n) for n in (5, 40, 70, 33)]
    kw = dict(max_batch=3, max_len=128, n_pages=24, page_size=32)
    with use_fused_gemms(fused):
        packed = PagedServeEngine(params, cfg, qcfg, **kw)
        _submit_all(packed, prompts)
        got = _results(packed)
        plain = PagedServeEngine(params, cfg, qcfg, **kw)
        plain.params = params
        _submit_all(plain, prompts)
        want = _results(plain)
    assert got == want
    assert packed.stats()["packed_weight_share"] == 1.0
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a), b),
                 params, before)


@pytest.mark.parametrize("prec,share", [("e4m3_bf16act", 1.0),
                                        ("e4m3_fwd_only", 1.0),
                                        ("bf16", 0.0)])
def test_packed_weight_share_and_what_stays_bf16(prec, share):
    """``packed_weight_share`` is 1.0 where the preset quantizes weights
    and 0.0 under bf16; packed leaves are MXWeights of 1 + 1/32 bytes a
    weight, the journal records both numbers once, and every leaf left
    unpacked (embedding table, norms, biases) is the caller's own array."""
    cfg, params = _tied_starcoder()
    eng = ServeEngine(params, cfg, preset(prec), max_batch=2, max_len=64)
    st = eng.stats()
    assert st["packed_weight_share"] == share
    (rec,) = eng.events.of_kind("pack_weights")
    assert rec["packed_weight_share"] == share
    ws = [p["w"] for p in jax.tree.leaves(
        params, is_leaf=lambda x: isinstance(x, dict) and "w" in x)
        if isinstance(p, dict)]
    n_weights = sum(w.size for w in ws) + params["embed"]["table"].size
    assert st["packed_weight_bytes"] == share * n_weights * (1 + 1 / 32)
    wq = eng.params["blocks"][0]["b0"]["attn"]["wq"]
    assert isinstance(wq["w"], MXWeight) == bool(share)
    assert wq["b"] is params["blocks"][0]["b0"]["attn"]["wq"]["b"]
    assert eng.params["embed"]["table"] is params["embed"]["table"]
    assert ("lm_head" in eng.params) == bool(share)
