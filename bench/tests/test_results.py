"""End-to-end numbers come from every sample of the window."""
import pytest

from conftest import ROOT

from bench.harness import execute, spec


def test_percentiles_over_every_sample_not_chunk_medians():
    cell = spec.resolve("starcoder2-3b.serve-chat", ROOT)
    # 17 fast gaps and 3 slow ones in each of 5 chunks: chunk medians
    # are all 10 ms, the p90 of all 100 samples is not.
    itl = ([0.010] * 17 + [0.500] * 3) * 5
    out = {"setup_s": 1.0, "window_s": 2.0,
           "window": {"tokens": 100, "ttft_s": [0.1] * 99 + [9.0],
                      "itl_s": itl}}
    tails = [m for m in cell.per_layer if m["name"].endswith("_ms")
             and m["name"].startswith("serve_")]
    m = execute.read_metrics(cell, cell.end_to_end + tails, out, {})
    assert m["serve_itl_p50_ms"]["value"] == pytest.approx(10.0)
    assert m["serve_itl_p90_ms"]["value"] == pytest.approx(500.0)
    assert m["serve_itl_p99_ms"]["value"] == pytest.approx(500.0)
    assert m["serve_tokens_per_s"]["value"] == pytest.approx(50.0)
    assert m["serve_ttft_p95_ms"]["value"] == pytest.approx(100.0)
    assert m["setup_s"] == {"value": 1.0, "unit": "s"}


def test_a_reader_with_nothing_to_read_leaves_its_metric_out():
    cell = spec.resolve("starcoder2-3b.serve-chat", ROOT)
    out = {"setup_s": 1.0, "window_s": 2.0,
           "window": {"tokens": 0, "ttft_s": [], "itl_s": []}}
    tails = [m for m in cell.per_layer if m["name"].endswith("p95_ms")]
    m = execute.read_metrics(cell, cell.end_to_end + tails, out, {})
    assert set(m) == {"setup_s", "serve_tokens_per_s"}


def test_a_program_loaded_from_the_cache_counts_as_a_compile(tmp_path):
    """A shape first met in the window is a missed warm-up whether it is
    compiled or loaded from the persistent cache."""
    import jax
    import jax.numpy as jnp

    from bench.harness.common import CompileCounter
    counter = CompileCounter()
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs,
              jax.config.jax_persistent_cache_min_entry_size_bytes)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        f = lambda x: jnp.sin(x) * 3 + 1  # noqa: E731
        n0 = counter.count()
        jax.jit(f)(jnp.ones(7)).block_until_ready()
        n1 = counter.count()
        jax.clear_caches()
        jax.jit(f)(jnp.ones(7)).block_until_ready()
        n2 = counter.count()
    finally:
        for k, v in zip(("jax_compilation_cache_dir",
                         "jax_persistent_cache_min_compile_time_secs",
                         "jax_persistent_cache_min_entry_size_bytes"),
                        before):
            jax.config.update(k, v)
    assert n1 > n0 and n2 > n1
