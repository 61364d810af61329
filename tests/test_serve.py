"""Serving engine parity + scheduler tests.

Pins the whole quantized decode path to oracles:

  * fused single-pass `lm_prefill` vs the token-stepped oracle
    (`prefill_into_cache`) — logits and cache, across the bf16 /
    e4m3_bf16act (paper Table-1 recipe) / mxfp8_e4m3 presets;
  * greedy continuation from either cache produces identical tokens;
  * per-row (vector) decode positions vs the legacy scalar form;
  * the continuous-batching scheduler is invariant to admission order and
    batch packing, and honors per-request sampling params / EOS /
    max-new-tokens / cache-exhaustion lifecycles.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import preset
from repro.models import init_cache, lm_decode_step, lm_init, lm_prefill
from repro.serve import (SamplingParams, ServeEngine, generate,
                         prefill_into_cache)

PRESETS = ("bf16", "e4m3_bf16act", "mxfp8_e4m3")
# bf16-activation presets agree to ~1 bf16 ulp.  With fully-quantized
# attention BMMs (mxfp8_e4m3) the two paths place MX blocks differently
# (flash quantizes the unnormalized online-softmax P per kv-chunk and V
# per chunk axis; token-stepping quantizes normalized probs and V over
# the whole cache axis), so their divergence is quantization noise by
# construction — asserted at that level in relative Frobenius norm.
ATOL = {"bf16": 5e-2, "e4m3_bf16act": 5e-2}

_SETUP = {}


def _setup(arch):
    if arch not in _SETUP:
        cfg = get_config(arch, "smoke")
        params = lm_init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 1,
                                  cfg.vocab, jnp.int32)
        _SETUP[arch] = (cfg, params, toks)
    return _SETUP[arch]


def _maxdiff(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def _rel_fro(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-9))


@pytest.mark.parametrize("prec", PRESETS)
@pytest.mark.parametrize("arch", ["qwen2-7b", "olmo-paper"])
def test_fused_prefill_matches_token_stepped(arch, prec):
    cfg, params, toks = _setup(arch)
    qcfg = preset(prec)
    lf, cf = lm_prefill(params, toks, cfg, qcfg, max_len=32)
    ls, cs = prefill_into_cache(params, toks, cfg, qcfg, max_len=32)
    if prec in ATOL:
        np.testing.assert_allclose(np.asarray(lf, np.float32),
                                   np.asarray(ls, np.float32),
                                   atol=ATOL[prec], rtol=ATOL[prec])
        for a, b in zip(jax.tree.leaves(cf), jax.tree.leaves(cs)):
            assert a.shape == b.shape and a.dtype == b.dtype
            assert _maxdiff(a, b) <= 8e-2
    else:
        assert _rel_fro(lf, ls) < 0.2
        a = np.asarray(lf, np.float32).ravel()
        b = np.asarray(ls, np.float32).ravel()
        cos = float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b)))
        assert cos > 0.98
        for a, b in zip(jax.tree.leaves(cf), jax.tree.leaves(cs)):
            assert a.shape == b.shape and a.dtype == b.dtype
            assert _rel_fro(a, b) < 0.15


@pytest.mark.parametrize("arch", ["recurrentgemma-9b", "xlstm-1.3b",
                                  "moonshot-v1-16b-a3b"])
def test_fused_prefill_windowed_and_recurrent_parity(arch):
    """Ring-buffer attention, recurrent/xLSTM state, and MoE stacks built
    in one fused pass match token-stepped warmup (scan-order / routing
    tolerance: both route dropless, but expanded prefill and absorbed
    decode round apart, which can flip a near-tie expert choice)."""
    try:
        cfg, params, toks = _setup(arch)
    except KeyError:
        pytest.skip(f"{arch} not registered")
    qcfg = preset("e4m3_bf16act")
    lf, cf = lm_prefill(params, toks, cfg, qcfg, max_len=32)
    ls, cs = prefill_into_cache(params, toks, cfg, qcfg, max_len=32)
    np.testing.assert_allclose(np.asarray(lf, np.float32),
                               np.asarray(ls, np.float32), atol=1e-1,
                               rtol=1e-1)
    for a, b in zip(jax.tree.leaves(cf), jax.tree.leaves(cs)):
        assert a.shape == b.shape, (a.shape, b.shape)
        assert _rel_fro(a, b) < 5e-2


@pytest.mark.parametrize("prec", ("bf16", "e4m3_bf16act"))
def test_greedy_continuation_identical_from_either_cache(prec):
    """Decoding greedily from the fused cache and from the token-stepped
    cache must produce the same tokens."""
    cfg, params, toks = _setup("qwen2-7b")
    qcfg = preset(prec)
    _, cf = lm_prefill(params, toks, cfg, qcfg, max_len=40)
    lf, cs = prefill_into_cache(params, toks, cfg, qcfg, max_len=40)
    T = toks.shape[1]
    step = jax.jit(lm_decode_step, static_argnums=(4, 5))

    def continue_greedy(logits, cache, n=8):
        out = []
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        for i in range(n):
            out.append(np.asarray(tok[:, 0]))
            logits, cache = step(params, cache, tok, jnp.int32(T + i), cfg,
                                 qcfg)
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        return np.stack(out, 1)

    lf2, _ = lm_prefill(params, toks, cfg, qcfg, max_len=40)
    np.testing.assert_array_equal(continue_greedy(lf2, cf),
                                  continue_greedy(lf, cs))


def test_decode_step_vector_pos_matches_scalar():
    """Per-row positions (continuous batching) reduce exactly to the
    legacy scalar form when all rows sit at the same position."""
    cfg, params, toks = _setup("qwen2-7b")
    qcfg = preset("mxfp8_e4m3")
    _, cache = prefill_into_cache(params, toks, cfg, qcfg, max_len=32)
    tok = toks[:, :1]
    T = toks.shape[1]
    l1, c1 = lm_decode_step(params, cache, tok, jnp.int32(T), cfg, qcfg)
    l2, c2 = lm_decode_step(params, cache, tok,
                            jnp.full((2,), T, jnp.int32), cfg, qcfg)
    np.testing.assert_array_equal(np.asarray(l1, np.float32),
                                  np.asarray(l2, np.float32))
    for a, b in zip(jax.tree.leaves(c1), jax.tree.leaves(c2)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def _run_engine(cfg, params, qcfg, prompts, order, max_batch, **kw):
    eng = ServeEngine(params, cfg, qcfg, max_batch=max_batch, max_len=64,
                      **kw)
    rmap = {}
    for i in order:
        sp = SamplingParams(
            temperature=0.0 if i % 2 == 0 else 0.8,
            top_k=0 if i % 2 == 0 else 50,
            max_new_tokens=5 + i, seed=100 + i)
        rmap[eng.submit(prompts[i], sp)] = i
    return {rmap[r.rid]: (r.tokens, r.finish_reason) for r in eng.drain()}


def test_scheduler_invariant_to_admission_order_and_packing():
    """Identical per-request results whatever the admission order, slot
    assignment, or batch width — the scheduler's core correctness
    property (per-request RNG streams + per-row positions)."""
    cfg, params, _ = _setup("qwen2-7b")
    qcfg = preset("e4m3_bf16act")
    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, cfg.vocab, size=n) for n in (5, 12, 3, 9, 17)]
    ref = _run_engine(cfg, params, qcfg, prompts, [0, 1, 2, 3, 4], 2)
    assert ref == _run_engine(cfg, params, qcfg, prompts, [4, 2, 0, 3, 1], 3)
    assert ref == _run_engine(cfg, params, qcfg, prompts, [0, 1, 2, 3, 4], 1)
    assert all(r == "length" for _, r in ref.values())
    assert all(len(t) == 5 + i for i, (t, _) in ref.items())


def test_prompt_bucketing_matches_exact_and_stepped_prefill():
    """Right-padding prompts to shape buckets must not change results:
    padded cache slots stay causally masked until overwritten."""
    cfg, params, _ = _setup("qwen2-7b")
    qcfg = preset("e4m3_bf16act")
    rng = np.random.RandomState(3)
    prompts = [rng.randint(1, cfg.vocab, size=n) for n in (4, 11, 19)]
    ref = _run_engine(cfg, params, qcfg, prompts, [0, 1, 2], 2)
    assert ref == _run_engine(cfg, params, qcfg, prompts, [0, 1, 2], 2,
                              bucket_prompts=False)
    assert ref == _run_engine(cfg, params, qcfg, prompts, [0, 1, 2], 2,
                              prefill="stepped")


def test_engine_eos_eviction():
    cfg, params, _ = _setup("qwen2-7b")
    qcfg = preset("e4m3_bf16act")
    prompt = np.arange(1, 9, dtype=np.int32)
    eng = ServeEngine(params, cfg, qcfg, max_batch=2, max_len=64)
    rid = eng.submit(prompt, SamplingParams(max_new_tokens=8))
    (ref,) = eng.drain()
    assert ref.rid == rid and ref.finish_reason == "length"
    eos = ref.tokens[2]        # force EOS at the 3rd greedy token
    eng2 = ServeEngine(params, cfg, qcfg, max_batch=2, max_len=64,
                       eos_id=eos)
    eng2.submit(prompt, SamplingParams(max_new_tokens=8))
    (r2,) = eng2.drain()
    assert r2.finish_reason == "eos"
    assert r2.tokens == ref.tokens[:3]


def test_engine_cache_full_eviction():
    cfg, params, _ = _setup("qwen2-7b")
    qcfg = preset("e4m3_bf16act")
    eng = ServeEngine(params, cfg, qcfg, max_batch=1, max_len=12)
    eng.submit(np.arange(1, 11, dtype=np.int32),
               SamplingParams(max_new_tokens=50))
    (r,) = eng.drain()
    assert r.finish_reason == "cache_full"
    assert len(r.tokens) == 3          # positions 10, 11 writable after T=10


def test_engine_top_k_one_equals_greedy():
    cfg, params, _ = _setup("qwen2-7b")
    qcfg = preset("e4m3_bf16act")
    prompt = np.arange(1, 7, dtype=np.int32)

    def tokens(sp):
        eng = ServeEngine(params, cfg, qcfg, max_batch=1, max_len=32)
        eng.submit(prompt, sp)
        return eng.drain()[0].tokens

    greedy = tokens(SamplingParams(temperature=0.0, max_new_tokens=6))
    topk1 = tokens(SamplingParams(temperature=1.3, top_k=1,
                                  max_new_tokens=6))
    assert greedy == topk1


def test_engine_events_and_stats():
    cfg, params, _ = _setup("qwen2-7b")
    qcfg = preset("e4m3_bf16act")
    eng = ServeEngine(params, cfg, qcfg, max_batch=2, max_len=32)
    for n in (4, 6, 9):
        eng.submit(np.arange(1, n + 1, dtype=np.int32),
                   SamplingParams(max_new_tokens=4))
    done = eng.drain()
    assert len(done) == 3
    kinds = [e["event"] for e in eng.events]
    assert kinds.count("submit") == 3
    assert kinds.count("prefill") == 3
    assert kinds.count("request_done") == 3
    pf = next(e for e in eng.events if e["event"] == "prefill")
    assert pf["fused"] and pf["time_s"] > 0
    dn = next(e for e in eng.events if e["event"] == "request_done")
    assert dn["reason"] == "length" and dn["latency_s"] > 0
    s = eng.stats()
    assert s["n_finished"] == 3
    assert s["decode_tok_s"] > 0 and s["prefill_tok_s"] > 0
    assert s["decode_tokens"] == sum(len(r.tokens) - 1 for r in done)


def test_generate_wrapper_roundtrip():
    cfg, params, _ = _setup("qwen2-7b")
    out = generate(params, jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]],
                                       jnp.int32), cfg,
                   preset("e4m3_bf16act"), max_new_tokens=5)
    assert out.shape == (2, 5)
    assert bool((out >= 0).all()) and bool((out < cfg.vocab).all())


# ---------------------------------------------------------------------------
# regression tests (PR 7 bugfixes)
# ---------------------------------------------------------------------------
def test_sample_tokens_top_k_keeps_exactly_k_under_ties():
    """Regression: the old `logit >= kth value` mask admitted every logit
    tied with the k-th best, inflating the candidate set beyond k.  With
    4 tied maxima and k=2, only the 2 lowest-index ties may ever win
    (stable sort breaks ties toward the lower index)."""
    from repro.serve import sample_tokens
    logits = jnp.asarray([[3.0, 3.0, 3.0, 3.0, 1.0, 0.0, -1.0, -2.0]])
    drawn = set()
    for seed in range(40):
        tok = sample_tokens(logits, jnp.asarray([1.0]), jnp.asarray([2]),
                            jnp.asarray([seed]), jnp.asarray([0]),
                            True, True)
        drawn.add(int(tok[0]))
    assert drawn == {0, 1}
    # k past the tie group: candidates are exactly the top 3 by rank.
    drawn = set()
    for seed in range(60):
        tok = sample_tokens(logits, jnp.asarray([5.0]), jnp.asarray([3]),
                            jnp.asarray([seed]), jnp.asarray([0]),
                            True, True)
        drawn.add(int(tok[0]))
    assert drawn == {0, 1, 2}


def test_scheduler_finish_zeroes_all_slot_state():
    """Regression: `_maybe_finish` used to leave temp/top_k/seeds/n_gen
    (and pos/cur_tok) behind, so a freed slot kept decoding stale tokens
    at a stale position until re-admission — and the paged engine keys
    live-row detection on this state being zero."""
    from repro.serve.scheduler import Request, Scheduler
    s = Scheduler(max_batch=2, max_len=32)
    req = Request(rid=0, prompt=np.arange(1, 5, dtype=np.int32),
                  sampling=SamplingParams(temperature=0.9, top_k=7,
                                          max_new_tokens=2, seed=123))
    assert not s.place(0, req, first_token=11, pos=4)
    assert s.temp[0] > 0 and s.top_k[0] == 7 and s.seeds[0] == 123
    finished = s.record_step(np.asarray([13, 0]))   # hits max_new_tokens
    assert finished == [req] and req.finish_reason == "length"
    for arr in (s.pos, s.cur_tok, s.temp, s.top_k, s.seeds, s.n_gen):
        assert arr[0] == 0


def test_admit_never_blocks_on_device_work(monkeypatch):
    """Regression: `_admit` called `jax.block_until_ready` per admission,
    serializing every prefill against the previous one's device work.  The
    two-phase admit (dispatch all, then realize) must not host-sync at
    all — first tokens are realized by the int() cast alone."""
    cfg, params, _ = _setup("qwen2-7b")
    calls = []
    orig = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: (calls.append(1), orig(x))[1])
    eng = ServeEngine(params, cfg, preset("e4m3_bf16act"), max_batch=3,
                      max_len=64)
    for n in (5, 9, 13):
        eng.submit(np.arange(1, n + 1, dtype=np.int32),
                   SamplingParams(max_new_tokens=3))
    done = eng.drain()
    assert len(done) == 3 and not calls


def test_submit_rejects_prompts_that_cannot_decode():
    """Regression: a prompt of exactly max_len used to burn a full prefill
    and then finish "cache_full" with its budget unspent.  submit() now
    rejects upfront unless max_new_tokens == 1 (the one shape that fits:
    prefill emits the first token, nothing more is decoded)."""
    cfg, params, _ = _setup("qwen2-7b")
    qcfg = preset("e4m3_bf16act")
    eng = ServeEngine(params, cfg, qcfg, max_batch=1, max_len=16)
    with pytest.raises(ValueError):
        eng.submit(np.arange(1, 18, dtype=np.int32))     # T = max_len + 1
    with pytest.raises(ValueError):
        eng.submit(np.arange(1, 17, dtype=np.int32),
                   SamplingParams(max_new_tokens=2))     # T = max_len
    with pytest.raises(ValueError):
        eng.submit(np.asarray([], dtype=np.int32))
    eng.submit(np.arange(1, 17, dtype=np.int32),
               SamplingParams(max_new_tokens=1))         # exact fit
    eng.submit(np.arange(1, 16, dtype=np.int32),
               SamplingParams(max_new_tokens=50))        # T = max_len - 1
    exact, almost = eng.drain()
    assert exact.finish_reason == "length" and len(exact.tokens) == 1
    assert almost.finish_reason == "cache_full" and len(almost.tokens) == 2


@pytest.mark.parametrize("prec", ("bf16", "mxfp8_e4m3"))
@pytest.mark.parametrize("arch", ["qwen2-7b", "recurrentgemma-9b"])
def test_decode_step_matches_prefill_last_token_fused(arch, prec):
    """Tq=1 decode-kernel parity on the fused path: prefilling T-1 tokens
    and taking one decode step must match the logits of prefilling all T
    tokens (global cache on qwen2, ring-buffer window on recurrentgemma),
    with both paths routed through mx_contract under use_fused_gemms."""
    from repro.core import use_fused_gemms
    cfg, params, toks = _setup(arch)
    qcfg = preset(prec)
    T = toks.shape[1]
    with use_fused_gemms(True):
        _, cache = lm_prefill(params, toks[:, :T - 1], cfg, qcfg,
                              max_len=32)
        ld, _ = lm_decode_step(params, cache, toks[:, T - 1:], T - 1, cfg,
                               qcfg)
        lp, _ = lm_prefill(params, toks, cfg, qcfg, max_len=32)
    ld = np.asarray(ld, np.float32)
    lp = np.asarray(lp, np.float32)
    if prec == "bf16":
        # 1e-1 as in the windowed/recurrent parity test above: rec-block
        # scan order differs between prefill and stepping in bf16.
        np.testing.assert_allclose(ld, lp, atol=1e-1, rtol=1e-1)
    else:
        # fully-quantized attention: decode quantizes P/V over the whole
        # cache axis, prefill per kv tile — divergence is MX block noise.
        assert _rel_fro(ld, lp) < 0.2
        a, b = ld.ravel(), lp.ravel()
        cos = float(a @ b / (np.linalg.norm(a) * np.linalg.norm(b)))
        assert cos > 0.98
