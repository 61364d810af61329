"""Moonlight-16B-A3B's mechanisms against the benchmark's plain reference
(``bench/harness/reference_deepseek_v3.py``, which imports nothing of the
program), at a small size with seeded weights on the CPU: latent attention
without q_lora, the DeepSeek-V3 sigmoid router, an expert-parallel share
of the experts, dropless serving routing, and chunked prefill then paged
decode through ``PagedServeEngine``.

Every comparison is in the precision the benchmark's cell states (E4M3
weights in blocks of 32 along the input, bfloat16 activations) unless it
says otherwise, and each tolerance gives its reason.  The program rounds
in other places than the reference (kernel accumulation order, bf16
element-wise ops), so where bf16 rounding is the only difference the
logits agree to a few bf16 steps of their own size; the same reference
with E2M1 weights, the next format below, lies well outside each
tolerance, which the tests check too.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.harness import arith_deepseek_v3 as arith  # noqa: E402
from bench.harness import reference_deepseek_v3 as ref  # noqa: E402
from bench.harness import weights_deepseek_v3 as weights  # noqa: E402
from bench.harness.reference import Precision  # noqa: E402

from repro.core import QuantConfig, preset  # noqa: E402
from repro.models import (LMConfig, init_cache_paged, lm_apply,  # noqa
                          lm_decode_step, lm_init, lm_prefill)
from repro.models.layers import qdense  # noqa: E402
from repro.models.mla import mla_apply, mla_init  # noqa: E402
from repro.models.mlp import mlp_apply  # noqa: E402
from repro.models.moe import moe_apply, route  # noqa: E402
from repro.serve import PagedServeEngine, SamplingParams  # noqa: E402
import repro.serve.engine as engine_mod  # noqa: E402

# The cell's model at a small size: every mechanism, with a held share
# (experts 4-7 of 16) that starts past expert 0.
MODEL = {"name": "moonlight-test", "n_layers": 3, "d_model": 64,
         "n_heads": 4, "n_kv_heads": 4, "d_head": 16, "d_ff": 128,
         "vocab": 256, "act": "swiglu", "norm": "rmsnorm", "mla": True,
         "q_lora": 0, "kv_lora": 32, "nope_dim": 16, "rope_dim": 16,
         "v_head": 16, "n_experts": 16, "experts_held": 4,
         "first_expert": 4, "top_k": 3, "n_shared": 2, "moe_dff": 32,
         "first_dense": 1, "router": "sigmoid", "routed_scale": 2.446,
         "rope_theta": 50000.0, "tie_embeddings": False}
STATED = Precision(weights="e4m3", act="bfloat16")
CONTROL = Precision(weights="e2m1", act="bfloat16")
QCFG = preset("e4m3_bf16act")
SEED = 2**32 + 5


def _cfg(**kw) -> LMConfig:
    return LMConfig(**{**MODEL, **kw})


_PARAMS = {}


def _params():
    if "p" not in _PARAMS:
        _PARAMS["p"] = weights.make_params(SEED, MODEL)
    return _PARAMS["p"]


def _tokens(shape, seed=0):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0,
                              MODEL["vocab"], jnp.int32)


def _ref_logits(params, toks, pr=STATED):
    """The reference's logits (B, T, V) and each position's routing tie
    margin (B, T)."""
    h, margin = ref.hidden_and_margins(params, toks, MODEL, q_chunk=16,
                                       pr=pr)
    return np.asarray(ref.logits(params, h, pr)), np.asarray(margin)


def _scale(x):
    return float(np.sqrt(np.mean(np.square(x))))


# Routing near a tie may pick other experts under other rounding: at this
# size the reference itself flips choices between float32 and bfloat16
# activations at margins up to about 0.011, and a flipped expert moves
# that token's logits by up to twice their RMS.  Positions whose
# reference routing, at an expert held here, comes within TIE of a tie in
# some layer are not judged; the rest are most of them.
TIE = 0.03
# Of the logits' RMS: bfloat16 rounding at other places than the
# reference's; the reference's own float32-vs-bfloat16-activation gap at
# untied positions is 4-7% of the RMS here, and the E2M1 control's is over
# 70%.
TOL = 0.10


def _judge(got, want, low, margin):
    """Logits (N, V) of the program, the reference and its E2M1 control at
    N positions with their tie margins (N,)."""
    clear = margin >= TIE
    assert clear.mean() >= 0.5, clear.mean()
    rms = _scale(want)
    err = np.abs(got - want).max(-1)[clear] / rms
    ctl = np.abs(low - want).max(-1)[clear] / rms
    assert err.max() < TOL, err.max()
    assert ctl.min() > TOL, ctl.min()


def test_weights_have_the_layout_lm_init_gives():
    got = jax.tree.map(lambda a: a.shape, weights.param_shapes(MODEL))
    want = jax.tree.map(lambda a: a.shape, jax.eval_shape(
        lambda: lm_init(jax.random.PRNGKey(0), _cfg())))
    assert got == want


def test_lm_apply_logits_match_the_reference():
    """The training forward (capacity ample: no token drops) against the
    reference's logits, by ``_judge``."""
    params = _params()
    cfg = _cfg(capacity_factor=16.0)
    toks = _tokens((2, 64))
    h, _ = jax.jit(lambda p, t: lm_apply(p, {"tokens": t}, cfg, QCFG))(
        params, toks)
    got = np.asarray(qdense(params["lm_head"], h, QCFG), np.float32)
    want, margin = _ref_logits(params, toks)
    low, _ = _ref_logits(params, toks, CONTROL)
    V = MODEL["vocab"]
    _judge(got.reshape(-1, V), want.reshape(-1, V), low.reshape(-1, V),
           margin.reshape(-1))


class _Recording(PagedServeEngine):
    """Keeps the logits behind every token it serves: a prefill's from its
    last chunk, a decode step's through a callback inside the step."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.seen = {}                  # rid -> [(position, logits)]
        self._rows = None

    def _first_token(self, logits, sp):
        req = self._jobs[0].req
        self.seen.setdefault(req.rid, []).append(
            (req.prompt.size - 1, np.asarray(logits[0], np.float32)))
        return super()._first_token(logits, sp)

    def _decode_batch(self, *a, **kw):
        self._rows = [(i, r.rid, int(self.sched.pos[i]))
                      for i, r in enumerate(self.sched.slots)
                      if r is not None]
        return super()._decode_batch(*a, **kw)

    def _fetch(self, nxt):
        out = super()._fetch(nxt)
        logits = _LOGITS.pop()
        for i, rid, pos in self._rows:
            self.seen[rid].append((pos, logits[i]))
        return out


_LOGITS = []


def _recorded_sample(logits, *a):
    jax.debug.callback(lambda l: _LOGITS.append(np.asarray(l, np.float32)),
                       logits)
    return _real_sample(logits, *a)


_real_sample = engine_mod.sample_tokens


def test_chunked_prefill_then_paged_decode_match_the_reference(monkeypatch):
    """Prompts of one chunk, of a chunk and a bit and of three chunks
    prefill by 32-token chunks into latent pages, then decode greedily
    two rows at a time; every served token's logits against the
    reference's full forward over the prompt and the tokens served before
    it, by ``_judge``."""
    monkeypatch.setattr(engine_mod, "sample_tokens", _recorded_sample)
    params = _params()
    cfg = _cfg(name="moonlight-test-recorded")
    eng = _Recording(params, cfg, QCFG, max_batch=2, max_len=128,
                     n_pages=16, page_size=32, chunk_size=32)
    assert eng.chunk
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, MODEL["vocab"], n) for n in (70, 33, 9)]
    for p in prompts:
        eng.submit(p, SamplingParams(max_new_tokens=12))
    done = {r.rid: r for r in eng.drain()}
    assert eng.stats()["moe.dropped"] == 0
    rows = []
    for rid, p in enumerate(prompts):
        toks = jnp.asarray(np.concatenate([p, done[rid].tokens[:-1]]),
                           jnp.int32)[None]
        want, margin = _ref_logits(params, toks)
        low, _ = _ref_logits(params, toks, CONTROL)
        pos = [q for q, _ in eng.seen[rid]]
        assert pos == list(range(p.size - 1, toks.shape[1]))
        rows.append((np.stack([g for _, g in eng.seen[rid]]),
                     want[0, pos], low[0, pos], margin[0, pos]))
    _judge(*(np.concatenate(x) for x in zip(*rows)))


def _hand_gate(logits, bias, k, scale):
    """The DeepSeek-V3 gate a token at a time, in numpy float64."""
    T, E = logits.shape
    out = np.zeros((T, E))
    for t in range(T):
        s = 1.0 / (1.0 + np.exp(-logits[t].astype(np.float64)))
        chosen = sorted(range(E), key=lambda e: -(s[e] + bias[e]))[:k]
        w = s[chosen] / (s[chosen].sum() + 1e-20)
        out[t, chosen] = w * scale
    return out


def test_v3_gate_matches_a_hand_written_one():
    """Selection on the biased scores, weights from the unbiased ones,
    normalized over the 6 and scaled by 2.446; float32 against float64,
    so 1e-6 of the gates' size."""
    E, k, D = 64, 6, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    p = {"router": jax.random.normal(ks[0], (D, E)) / np.sqrt(D),
         "router_bias": 0.1 * jax.random.normal(ks[1], (E,))}
    x = jax.random.normal(ks[2], (48, D))
    gates, idx, _ = route(p, x, top_k=k, router="sigmoid",
                          routed_scale=2.446)
    got = np.zeros((48, E))
    np.put_along_axis(got, np.asarray(idx), np.asarray(gates), axis=-1)
    logits = np.asarray(x, np.float64) @ np.asarray(p["router"], np.float64)
    want = _hand_gate(logits, np.asarray(p["router_bias"]), k, 2.446)
    np.testing.assert_allclose(got, want, atol=1e-6)
    ref_g, _ = ref.gates(p, x, {"top_k": k, "routed_scale": 2.446})
    np.testing.assert_allclose(np.asarray(ref_g), want, atol=1e-6)


def test_the_correction_bias_moves_selections():
    """The seeded bias (the benchmark's draw) is no bystander: without it
    at least 10% of the top-6 choices over 64 experts differ."""
    m = {**MODEL, "n_experts": 64, "experts_held": 8, "first_expert": 0,
         "top_k": 6}
    moe = weights.make_params(SEED, m)["blocks"][1]["b0"]["moe"]
    p = {"router": moe["router"][0], "router_bias": moe["router_bias"][0]}
    x = jax.random.normal(jax.random.PRNGKey(9), (256, MODEL["d_model"]))
    _, idx, _ = route(p, x, top_k=6, router="sigmoid")
    _, idx0, _ = route(dict(p, router_bias=jnp.zeros(64)), x, top_k=6,
                       router="sigmoid")
    a, b = np.asarray(idx), np.asarray(idx0)
    changed = np.mean([len(set(a[t]) - set(b[t])) / 6.0
                       for t in range(len(a))])
    assert changed >= 0.10, changed


def _uncut(m):
    """All 16 experts held on one chip: the layer before the cut."""
    return {**m, "experts_held": m["n_experts"], "first_expert": 0}


def test_expert_parallel_shares_sum_to_the_uncut_layer():
    """Eight shares of two experts each, every one routing over all 16:
    their routed parts plus the shared experts, counted once, give the
    uncut reference layer.  In float32 with no MX rounding, so only the
    summation order differs: 1e-5 of the output's RMS."""
    m = _uncut(MODEL)
    layer = weights.make_params(SEED, m, jnp.float32)["blocks"][1]["b0"]
    moe = jax.tree.map(lambda a: a[0], layer["moe"])
    shared = jax.tree.map(lambda a: a[0], layer["shared"])
    x = jax.random.normal(jax.random.PRNGKey(4), (40, MODEL["d_model"]))
    bf16 = QuantConfig.bf16()
    parts = []
    for first in range(0, 16, 2):
        share = {k: v[first:first + 2] if k.startswith("w_") else v
                 for k, v in moe.items()}
        y, metrics = moe_apply(share, x, bf16, top_k=m["top_k"],
                               router="sigmoid", routed_scale=2.446,
                               first_expert=first, dropless=True)
        assert int(metrics["dropped"]) == 0
        parts.append(np.asarray(y))
    got = sum(parts) + np.asarray(mlp_apply(shared, x, bf16, "swiglu"))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.held_experts(moe, x, m, ref.FP32)
                          + ref.swiglu(ref._flat(shared), x, ref.FP32))
    np.testing.assert_allclose(got, want, atol=1e-5 * _scale(want))


def test_dropless_routing_keeps_every_token_on_one_expert():
    """A bias that sends every token to expert 5 (held here): serving's
    dropless routing still matches the reference layer, where training's
    capacity drops tokens; the engine's counter stays 0.  Float32, no MX
    rounding: 1e-5 of the output's RMS."""
    params = _params()
    moe = jax.tree.map(lambda a: a[0], params["blocks"][1]["b0"]["moe"])
    moe = jax.tree.map(lambda a: a.astype(jnp.float32), moe)
    moe["router_bias"] = moe["router_bias"].at[5].set(100.0)
    x = jax.random.normal(jax.random.PRNGKey(8), (64, MODEL["d_model"]))
    kw = dict(top_k=3, router="sigmoid", routed_scale=2.446, first_expert=4)
    y, metrics = moe_apply(moe, x, QuantConfig.bf16(), dropless=True, **kw)
    assert int(metrics["held_tokens"][1]) == 64
    assert int(metrics["dropped"]) == 0
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.held_experts(moe, x, MODEL, ref.FP32))
    np.testing.assert_allclose(np.asarray(y), want,
                               atol=1e-5 * _scale(want))
    _, capped = moe_apply(moe, x, QuantConfig.bf16(), **kw)
    assert int(capped["dropped"]) > 0

    params = jax.tree.map(lambda a: a, params)
    blk = params["blocks"][1]["b0"]["moe"]
    params["blocks"][1]["b0"]["moe"] = dict(
        blk, router_bias=blk["router_bias"].at[:, 5].set(100.0))
    eng = PagedServeEngine(params, _cfg(), QCFG, max_batch=2, max_len=64,
                           n_pages=8, page_size=32, chunk_size=32)
    for n in (40, 12):
        eng.submit(np.arange(n) % MODEL["vocab"],
                   SamplingParams(max_new_tokens=3))
    eng.drain()
    s = eng.stats()
    assert s["moe.dropped"] == 0
    # every real token routed to expert 5 in both MoE layers, and nothing
    # else: (40 + 12 prompt + 2 * 2 decoded) x 2 layers x (1 + the other
    # held picks)
    assert s["moe.held_tokens"] >= 2 * (40 + 12 + 4)


def test_mla_without_q_lora_matches_the_reference():
    """Queries by one projection: ``w_q`` and no ``w_dq``/``w_uq``; the
    expanded forward in float32 with no rounding matches the reference's
    to 1e-5 of its RMS (summation order only)."""
    D, H = MODEL["d_model"], MODEL["n_heads"]
    p = mla_init(jax.random.PRNGKey(2), D, H, 0, 32, 16, 16, 16)
    assert "w_q" in p and "w_dq" not in p and "w_uq" not in p
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, D))
    pos = jnp.broadcast_to(jnp.arange(24)[None], (2, 24))
    cfg = _cfg()
    got = mla_apply(p, x, qcfg=QuantConfig.bf16(), n_heads=H, nope=16,
                    rope_dim=16, v_head=16, positions=pos,
                    spec=cfg.attn_spec(), rope_theta=5e4)
    with jax.default_matmul_precision("highest"):
        want = ref.mla(p, x, MODEL, jnp.arange(24), 8, ref.FP32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5 * _scale(np.asarray(want)))


@pytest.mark.parametrize("prec", ("e4m3_bf16act", "e5m2_bf16act"))
def test_slab_decode_paged_decode_and_prefill_agree(prec):
    """Under a weight-MX preset the absorbed decode decompresses with the
    same MX-rounded W_uk / W_uv that the expanded prefill's GEMMs use, so
    a decode step after T-1 prefilled tokens gives the logits of
    prefilling all T, on the slab cache and on latent pages alike, to fp
    associativity: the two forms sum in other orders and round to
    bfloat16 at other points (the expanded keys and values, the absorbed
    context), 2-3.5% of the logits' RMS over four seeds (one row 6.3%);
    5% is the limit.  Decoding with the raw weights gave 7-57%."""
    cfg = _cfg(name="moonlight-test-decode")
    qcfg = preset(prec)
    params = lm_init(jax.random.PRNGKey(0), cfg)
    toks = _tokens((2, 24), seed=1)
    T = toks.shape[1]
    _, cache = lm_prefill(params, toks[:, :T - 1], cfg, qcfg, max_len=32)
    slab, _ = lm_decode_step(params, cache, toks[:, T - 1:], T - 1, cfg,
                             qcfg)
    paged_cache = init_cache_paged(cfg, 2, 32, 4, 16)
    paged_cache = jax.tree.map(
        lambda pool, c: pool.at[:, :].set(
            c.reshape((c.shape[0], 4, 16) + c.shape[3:]).astype(pool.dtype)),
        paged_cache, cache)
    pt = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    paged, _ = lm_decode_step(params, paged_cache, toks[:, T - 1:], T - 1,
                              cfg, qcfg, page_table=pt, page_size=16)
    full, _ = lm_prefill(params, toks, cfg, qcfg, max_len=32)
    slab, paged, full = (np.asarray(a, np.float32)
                         for a in (slab, paged, full))
    tol = 0.05 * _scale(full)
    np.testing.assert_array_equal(slab, paged)
    assert np.abs(slab - full).max() < tol


def test_arithmetic_of_a_hand_counted_layer():
    """One token of the test model, counted by hand: MLA projections
    64x128 (w_q) + 64x32 + 64x16 + 32x64 (w_uk) + 32x64 (w_uv) + 64x64
    (wo) = 19,456 MACs; dense MLP 3 x 64 x 128 = 24,576; shared experts
    3 x 64 x 64 = 12,288; router 64 x 16 = 1,024; routed experts 3 x 64
    x 32 = 6,144 each at 3 x 4/16 = 0.75 held a token; head 64 x 256 =
    16,384.  Decode attention over 10 positions: 4 heads x 10 x 2 x (2 x
    32 + 16) a layer."""
    macs = (19456 + 24576) + 2 * (19456 + 12288 + 1024 + 0.75 * 6144) \
        + 16384
    assert arith.token_gemm_flops(MODEL) == 2.0 * macs
    assert arith.decode_token_flops(MODEL, 10) == 2.0 * macs + 3 * 4 * 10 \
        * 2 * (2 * 32 + 16)
    assert arith.prefill_flops(MODEL, 4) == 4 * 2.0 * macs + 3 * 4 * 10 \
        * 2 * (16 + 16 + 16)
    peak = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
    # one expert with 3 tokens in one layer: three GEMMs, memory-bound
    want = 3 * (64 * 32 * (1 + 1 / 32) + 3 * 96 * 2) / 1e11
    assert arith.expert_gemm_ideal_s(MODEL, [[0, 3, 0, 0]], peak) \
        == pytest.approx(want)
