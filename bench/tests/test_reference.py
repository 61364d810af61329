"""The seeded weights have the program's layout, and the reference
computes what the program computes (at a small size on the CPU): in
float32 against the program in bf16, and with E4M3 weights against the
program in ``e4m3_bf16act``, far closer than the program's E2M1 weights."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import TINY_MODEL

from bench.harness import reference, spec, weights



def _tokens(seed, b, t, vocab):
    return jax.random.randint(jax.random.PRNGKey(seed), (b, t), 0, vocab,
                              jnp.int32)


@pytest.mark.parametrize("tie", [False, True])
def test_weights_have_the_program_layout(tie):
    from repro.models import lm_init
    m = dict(TINY_MODEL, tie_embeddings=tie, out_bias=False)
    ours = weights.param_shapes(m)
    theirs = jax.eval_shape(lambda k: lm_init(k, spec.lm_config({"model": m})),
                            jax.random.PRNGKey(0))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    assert jax.tree.map(lambda a: a.shape, ours) == \
        jax.tree.map(lambda a: a.shape, theirs)


def test_wide_seeds_differ_and_repeat():
    a, b = weights.key(2**33 + 1), weights.key(2**33 + 1)
    c = weights.key(1)
    assert np.array_equal(jax.random.key_data(a), jax.random.key_data(b))
    assert not np.array_equal(jax.random.key_data(a), jax.random.key_data(c))


def _program(m, toks, name):
    """The program's logits at the last position of each row."""
    from repro.core import preset
    from repro.models import lm_prefill
    cfg = spec.lm_config({"model": m})
    return lambda p: lm_prefill(p, toks, cfg, preset(name),
                                toks.shape[1])[0].astype(jnp.float32)


def _reference(m, toks, pr=reference.FP32):
    def fn(p):
        h = reference.hidden(p, toks, m, pr=pr)
        return reference.logits(p, h[:, -1], pr)
    return fn


def _gap(a, b):
    """Widest logit gap, over the spread of the reference's logits."""
    return float(jnp.max(jnp.abs(a - b)) / jnp.std(b))


def test_reference_logits_match_the_program():
    """Every layer's weights, the output and MLP biases among them: a
    program that left a bias out would move the logits."""
    m = TINY_MODEL
    assert m["out_bias"]
    p = weights.make_params(5, m)
    toks = _tokens(6, 2, 64, m["vocab"])
    ref = jax.jit(_reference(m, toks))(p)
    assert _gap(jax.jit(_program(m, toks, "bf16"))(p), ref) < 0.1
    nob = jax.tree_util.tree_map_with_path(
        lambda k, x: x * 0 if "b" == getattr(k[-1], "key", None)
        and "mlp" in jax.tree_util.keystr(k) else x, p)
    assert _gap(jax.jit(_reference(m, toks))(nob), ref) > 0.3


def test_mx_reference_follows_the_mx_program():
    m = TINY_MODEL
    pr = reference.Precision(weights="e4m3", act="bfloat16")
    p = weights.make_params(7, m)
    toks = _tokens(8, 4, 64, m["vocab"])
    ref = jax.jit(_reference(m, toks, pr))(p)
    sound = _gap(jax.jit(_program(m, toks, "e4m3_bf16act"))(p), ref)
    low = _gap(jax.jit(_program(m, toks, "e2m1_bf16act"))(p), ref)
    assert sound < 0.05 and low > 3 * sound, (sound, low)


def test_mx_round_e2m1_grid():
    x = jnp.array([[0.0, 0.26, 0.74, 1.3, 2.6, 5.1, 7.9, -3.2] * 4])
    y = reference.mx_round(x, -1, "e2m1")
    # amax 7.9 -> scale 2^(2-2) = 1: the E2M1 grid, saturating at 6
    assert y[0, :8].tolist() == [0.0, 0.5, 0.5, 1.5, 3.0, 6.0, 6.0, -3.0]


def test_mx_round_e4m3_saturates_the_last_bin():
    # amax 511 -> scale 2^(8-8) = 1: 511 lies past 448 and saturates
    x = jnp.array([[511.0, 1.0, 0.0625, -3.3] + [0.0] * 28])
    y = reference.mx_round(x, -1, "e4m3")
    assert y[0, :4].tolist() == [448.0, 1.0, 0.0625, -3.25]
