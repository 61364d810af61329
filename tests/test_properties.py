"""Property-based tests (hypothesis): MX quantizer algebra + watchdog.

Extends the 1-D floor-mode properties in test_mx_formats.py with the
invariants the serving/training stack actually leans on, across all
scale modes:

  * idempotence      Q(Q(x)) == Q(x)          (re-serving quantized
                     weights is a no-op);
  * sign preservation  sign(Q(x)) in {0, sign(x)};
  * per-block scale invariance  Q(x * 2^k) == Q(x) * 2^k for block-wise
    positive power-of-two rescaling (the shared exponent absorbs it);
  * SpikeDetector never flags a monotonically decreasing loss series
    (the recovery policy cannot fire on healthy training).
"""
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis",
    reason="property tests need hypothesis (pip install -e .[test])")
from hypothesis import given, settings, strategies as st  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import SpikeDetector, get_format, quantize_mx  # noqa: E402

FMTS = st.sampled_from(["e4m3", "e5m2", "e2m3", "e3m2", "e2m1"])
MODES = st.sampled_from(["floor", "bump", "adaptive"])
BLOCK = 8


def f32(v: float) -> float:
    """``v`` rounded to float32: width=32 strategies refuse bounds that
    float32 cannot represent."""
    return float(np.float32(v))


@st.composite
def blocked_arrays(draw, n_blocks_max=4):
    """(n_blocks, BLOCK) fp32 with magnitudes well inside the shared-
    exponent clip range (so scale arithmetic is exact)."""
    nb = draw(st.integers(1, n_blocks_max))
    elem = st.one_of(st.just(0.0), st.floats(f32(0.01), 64.0, width=32),
                     st.floats(-64.0, f32(-0.01), width=32))
    vals = draw(st.lists(elem, min_size=nb * BLOCK, max_size=nb * BLOCK))
    return np.asarray(vals, np.float32).reshape(nb, BLOCK)


@given(x=blocked_arrays(), fmt=FMTS, mode=MODES)
@settings(max_examples=60, deadline=None)
def test_quantize_idempotent_all_scale_modes(x, fmt, mode):
    f = get_format(fmt)
    q1 = quantize_mx(jnp.asarray(x), f, axis=-1, block=BLOCK,
                     scale_mode=mode)
    q2 = quantize_mx(q1, f, axis=-1, block=BLOCK, scale_mode=mode)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))


@given(x=blocked_arrays(), fmt=FMTS, mode=MODES)
@settings(max_examples=60, deadline=None)
def test_quantize_preserves_sign(x, fmt, mode):
    q = np.asarray(quantize_mx(jnp.asarray(x), get_format(fmt), axis=-1,
                               block=BLOCK, scale_mode=mode))
    # never flips sign (may flush small magnitudes to zero)
    assert (np.sign(q) * np.sign(x) >= 0).all()
    # and never zeroes a block's max (the value that sets the scale)
    m = np.abs(x).max(-1)
    qm = np.abs(q).max(-1)
    assert (qm[m > 0] > 0).all()


@given(x=blocked_arrays(), fmt=FMTS, mode=MODES,
       data=st.data())
@settings(max_examples=60, deadline=None)
def test_blockwise_power_of_two_scale_invariance(x, fmt, mode, data):
    """Rescaling each block by its own positive power of two shifts the
    shared exponent and nothing else: Q(x * 2^k) == Q(x) * 2^k."""
    nb = x.shape[0]
    ks = np.asarray(data.draw(st.lists(st.integers(-6, 6), min_size=nb,
                                       max_size=nb)), np.int32)
    s = (2.0 ** ks)[:, None].astype(np.float32)
    f = get_format(fmt)
    q = np.asarray(quantize_mx(jnp.asarray(x), f, axis=-1, block=BLOCK,
                               scale_mode=mode))
    qs = np.asarray(quantize_mx(jnp.asarray(x * s), f, axis=-1, block=BLOCK,
                                scale_mode=mode))
    np.testing.assert_array_equal(qs, q * s)


@given(losses=st.lists(st.floats(f32(1e-3), 1e3, allow_nan=False,
                                 width=32),
                       min_size=1, max_size=100),
       factor=st.floats(1.5, 1e3))
@settings(max_examples=60, deadline=None)
def test_spike_detector_never_flags_decreasing_losses(losses, factor):
    """App.-B heuristic sanity: a monotonically decreasing finite loss
    series can never trip the watchdog (no false-positive rollbacks on
    healthy runs), for any spike factor > 1."""
    series = sorted(set(float(l) for l in losses), reverse=True)
    det = SpikeDetector(spike_factor=factor)
    for loss in series:
        assert not det.update(loss)
    assert det.n_spikes == 0


@given(losses=st.lists(st.floats(0.5, 10.0, allow_nan=False, width=32),
                       min_size=2, max_size=50))
@settings(max_examples=30, deadline=None)
def test_spike_detector_always_flags_giant_spike(losses):
    """...and a loss 1000x above everything seen always trips it."""
    det = SpikeDetector(spike_factor=100.0)
    for loss in losses:
        det.update(float(loss))
    assert det.update(1000.0 * max(losses))


# ---------------------------------------------------------------------------
# sweep-engine lane parity (the statistic-validity property: a vmapped
# sweep lane must behave exactly like a standalone run of that cell)
# ---------------------------------------------------------------------------
@st.composite
def small_grids(draw):
    """Random tiny sweep grids: 1-3 lanes over random (seed, lr), one
    random proxy shape and scheme, short horizons."""
    import dataclasses

    from repro.sweep import RunSpec

    base = RunSpec(
        kind="proxy",
        d_model=draw(st.sampled_from([16, 32])),
        n_layers=draw(st.integers(1, 2)),
        batch_size=32,
        steps=draw(st.integers(3, 8)),
        scheme=draw(st.sampled_from(["bf16", "mxfp8_e4m3", "mxfp6_e2m3"])),
        teacher_seed=draw(st.integers(0, 3)),
        spike_factor=10.0)
    n = draw(st.integers(1, 3))
    seeds = draw(st.lists(st.integers(0, 5), min_size=n, max_size=n,
                          unique=True))
    lrs = draw(st.lists(st.sampled_from([5e-4, 1e-3, 2e-3]),
                        min_size=n, max_size=n))
    return [dataclasses.replace(base, seed=s, lr=lr)
            for s, lr in zip(seeds, lrs)]


@given(runs=small_grids())
@settings(max_examples=8, deadline=None)
def test_sweep_lane_parity_property(runs):
    """Each vmapped lane matches a standalone train_simple-style run of
    the same (seed, lr, qcfg) to tight tolerance, spike flags included —
    no leakage through the batched detector or shared RNG streams."""
    import jax

    from repro.core import SpikeDetector, preset
    from repro.models import (ProxyConfig, proxy_batch, proxy_init,
                              proxy_loss, teacher_init)
    from repro.optim import AdamWConfig, adamw_init, adamw_update
    from repro.sweep import run_sweep

    rep = run_sweep(runs, keep_history=True)
    r0 = runs[0]
    cfg = ProxyConfig(d_model=r0.d_model, n_layers=r0.n_layers,
                      batch_size=r0.batch_size)
    opt_cfg = AdamWConfig(weight_decay=0.0, grad_clip=0.0)
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, b, q: proxy_loss(p, b, cfg, q)[0]), static_argnums=(2,))
    for r in runs:
        teacher = teacher_init(jax.random.PRNGKey(r.teacher_seed), cfg)
        params = proxy_init(jax.random.PRNGKey(r.seed), cfg)
        opt = adamw_init(params, opt_cfg)
        qcfg = preset(r.scheme)
        det = SpikeDetector(spike_factor=r.spike_factor,
                            window=r.spike_window)
        ref_losses, ref_flags = [], []
        for step in range(r.steps):
            batch = proxy_batch(step, teacher, cfg,
                                seed=r.effective_data_seed)
            loss, grads = grad_fn(params, batch, qcfg)
            params, opt, _ = adamw_update(grads, opt, params, r.lr,
                                          opt_cfg)
            ref_losses.append(float(loss))
            ref_flags.append(det.update(float(loss)))
        hist = rep[r.run_id].history
        np.testing.assert_allclose(hist["loss"], ref_losses, rtol=2e-4,
                                   atol=1e-7)
        assert hist["spike_flags"] == ref_flags


# ---------------------------------------------------------------------------
# guard policy hysteresis (repro.guard.policy)
# ---------------------------------------------------------------------------
signal_values = st.one_of(
    st.floats(width=32, allow_nan=True, allow_infinity=True),
    st.just(float("nan")), st.just(float("inf")))


@given(trace=st.lists(signal_values, min_size=1, max_size=200),
       cooldown=st.integers(1, 20), window=st.integers(1, 50))
@settings(max_examples=80, deadline=None)
def test_guard_policy_cannot_flap(trace, cooldown, window):
    """For ANY signal trace: a policy with cooldown c performs at most
    ceil(T/c) transitions over T steps, consecutive transitions are >= c
    steps apart, and it never oscillates A -> B -> A within one stability
    window (the revisit lock)."""
    from repro.guard import GuardPolicy, PolicyState, Rule, decide

    pol = GuardPolicy(rules=(Rule("x", 1.0, calm=0.5),),
                      cooldown=cooldown, stability_window=window,
                      max_transitions=1 << 30)
    state = PolicyState()
    transitions = []
    for t, v in enumerate(trace):
        state, dec = decide(pol, state, t, {"x": v})
        if dec is not None:
            transitions.append((t, dec.from_level, dec.to_level))

    T = len(trace)
    assert len(transitions) <= -(-T // cooldown)       # ceil(T / c)
    for (t1, _, _), (t2, _, _) in zip(transitions, transitions[1:]):
        assert t2 - t1 >= cooldown
    # revisit lock: a transition returning to the level just left must be
    # at least one stability window after the transition that left it
    for (t1, a1, b1), (t2, a2, b2) in zip(transitions, transitions[1:]):
        assert a2 == b1                                # levels chain
        if b2 == a1:
            assert t2 - t1 >= window


@given(trace=st.lists(st.floats(0.0, 10.0, width=32), min_size=5,
                      max_size=120),
       budget=st.integers(0, 4))
@settings(max_examples=40, deadline=None)
def test_guard_rule_budget_bounds_escalations(trace, budget):
    """A rule with a firing budget causes at most that many escalations,
    no matter how hostile the trace."""
    from repro.guard import GuardPolicy, PolicyState, Rule, decide

    pol = GuardPolicy(rules=(Rule("x", 1.0, calm=0.5, budget=budget),),
                      cooldown=1, stability_window=1,
                      max_transitions=1 << 30, deescalate=False)
    state = PolicyState()
    n_esc = 0
    for t, v in enumerate(trace):
        state, dec = decide(pol, state, t, {"x": v})
        n_esc += dec is not None and dec.kind == "escalate"
    assert n_esc <= budget


# ---------------------------------------------------------------------------
# Flash-attention kernel == oracle for arbitrary (non-multiple) Tq/Tk
# ---------------------------------------------------------------------------
def _attention_f64(q, k, v, causal: bool, q_offset: int):
    """Float64 softmax attention of q (B, G, Tq, d) over k/v (B, Tk, d),
    query i at position i + q_offset, and per element the scale
    sum_j p_j |v_j| / l its fp32 rounding is measured in."""
    q, k, v = (np.asarray(t, np.float64) for t in (q, k, v))
    s = np.einsum("bgqd,bkd->bgqk", q, k) / np.sqrt(q.shape[-1])
    tq, tk = s.shape[-2:]
    ok = np.ones((tq, tk), bool)
    if causal:
        ok = (np.arange(tq)[:, None] + q_offset) >= np.arange(tk)[None]
    s = np.where(ok, s, -np.inf)
    m = np.max(s, axis=-1, keepdims=True)
    p = np.exp(s - np.where(np.isfinite(m), m, 0.0))
    l = np.sum(p, axis=-1, keepdims=True)
    l = np.where(l > 0, l, 1.0)
    return (np.einsum("bgqk,bkd->bgqd", p, v) / l,
            np.einsum("bgqk,bkd->bgqd", p, np.abs(v)) / l)


@given(tq=st.integers(1, 70), tk=st.integers(1, 70),
       causal=st.booleans(), quant=st.booleans(), data=st.data())
@settings(max_examples=25, deadline=None)
def test_flash_attention_kernel_equals_oracle_any_shape(tq, tk, causal,
                                                        quant, data):
    """The Pallas flash kernel (interpret mode) must match the jnp oracle
    for arbitrary Tq/Tk — including shapes that are not tile multiples
    (padding), Tq > Tk with a query offset, and fully masked rows.

    Tolerance note: at VPU-aligned tiles the match is bitwise (enforced in
    test_kernels.py), but for other shapes XLA:CPU picks other dot and
    exp/log code paths on the two sides (packet math vs a scalar remainder
    loop, other accumulation orders), so their fp32 roundings differ.
    Unquantized, the property is that each side is as accurate as fp32
    allows: within 16 roundings of a float64 softmax, in units of
    eps * sum_j p_j |v_j| / l, the scale of the row's own values (errors
    measured over a few hundred random shapes stay under 6 such units; a
    masking, tiling or offset defect is ~1e7 of them).  Both sides are held
    to the float64 answer, not only to each other, so an error they share
    fails too.  Quantized, a 1-ulp difference in p can cross an e4m3
    rounding boundary and flip one mantissa step (2^-3 relative), so for
    MX formats the property asserts a tight logsumexp bound (the score
    path — any masking/tiling/offset defect lands here as an O(1) error)
    plus a small relative-Frobenius bound on the output (rounding-flip
    noise is ~1e-2; a wrong-tile PV bug is O(1)).  The oracle is jitted so
    both sides share one compilation regime — eager-vs-jit already differs
    at the same amplified scale for the oracle alone.
    """
    from repro.core import AttnSpec, E4M3
    from repro.kernels import mx_flash_attention, mx_flash_attention_ref
    q_offset = data.draw(st.integers(0, 16)) if causal else 0
    spec = AttnSpec.training(causal=causal, window=0, q_chunk=32,
                             kv_chunk=32, q_offset=q_offset)
    rng = np.random.RandomState(data.draw(st.integers(0, 2 ** 16)))
    d = 32
    q = jnp.asarray(rng.randn(1, 2, tq, d).astype(np.float32))
    k = jnp.asarray(rng.randn(1, tk, d).astype(np.float32))
    v = jnp.asarray(rng.randn(1, tk, d).astype(np.float32))
    fmt = E4M3 if quant else None
    oracle = jax.jit(mx_flash_attention_ref, static_argnames=("fmt", "spec"))
    o_k, l_k = mx_flash_attention(q, k, v, fmt, spec)
    o_r, l_r = oracle(q, k, v, fmt, spec)
    o_k, l_k, o_r, l_r = (np.asarray(x) for x in (o_k, l_k, o_r, l_r))
    np.testing.assert_allclose(l_k, l_r, rtol=3e-7, atol=1e-5)
    if fmt is None:
        o64, scale = _attention_f64(q, k, v, causal, q_offset)
        tol = 16 * np.finfo(np.float32).eps * scale
        for name, o in (("kernel", o_k), ("oracle", o_r)):
            err = np.abs(o - o64)
            assert (err <= tol).all(), (
                f"{name}: {float(np.max(err / np.maximum(tol, 1e-45)))} x "
                f"the fp32 rounding budget from the float64 softmax")
    else:
        denom = max(float(np.linalg.norm(o_r)), 1e-30)
        assert float(np.linalg.norm(o_k - o_r)) / denom < 0.05
