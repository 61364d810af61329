"""The serving driver for a DeepSeek-V3-style model with experts: the
program's ``PagedServeEngine`` on seeded bf16 weights
(``harness/weights_deepseek_v3.py``), driven by the mix's clients on the
benchmark's own clock.

It runs as ``drivers/serve.py`` does, with that driver's ``Load``, window
reduction and reference rows: set-up makes the weights and the engine,
runs the generator's warm-up prompts (every chunk start the traffic
reaches), then the fill; the window opens once every request sent at the
start has its first token.  Beside that, it keeps each step's routing:
the (moe_layers, held) tokens that the step's decode routed to the
experts this chip holds (``engine.decode_routing``), which the metrics of
the expert layer read, and the engine's ``moe.dropped`` counter, which
``correct`` holds to 0.  The engine alone holds the weights in the
window; the reference's copy is made again from the seed after the engine
is gone.

After the window the reference (``harness/reference_deepseek_v3.py``)
scores a sample of the finished requests, the longest among them: for
each served token, how far its logit lies below the reference's best at
that position.  ``greedy_gap`` is the 95th percentile of those gaps,
not the widest as in ``drivers/serve.py``: the program and the
reference round apart, so a token whose routing sits within a rounding
of a tie may take another expert in one of them, and the widest gap
then reads that flip rather than a fault.  Model FLOPs are this chip's
active FLOPs (``harness/arith_deepseek_v3.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time
from typing import Any, Dict, List, Optional

from bench.harness import arith_deepseek_v3 as arith
from bench.harness import reference_deepseek_v3 as reference
from bench.harness import weights_deepseek_v3 as weights
from bench.harness.common import CompileCounter, memory_peak, percentile
from bench.harness.lengths import sample_indices
from bench.harness.reference import Precision
from bench.harness.spec import Cell, lm_config, load
from bench.harness.trace import traced, window_s

serve = load("drivers", "serve")


class Load(serve.Load):
    """The mix's clients, keeping beside each step (``steps``) the tokens
    its decode routed to each held expert of each MoE layer, or None where
    the step decoded nothing."""

    def __init__(self, engine, traffic):
        super().__init__(engine, traffic)
        self.routing: List[Optional[Any]] = []

    def step(self) -> None:
        super().step()
        decoded = self.steps[-1][2] > 0
        self.routing.append(self.engine.decode_routing if decoded else None)


def model_flops(loop: Load, t0: float, t1: float, m: Dict[str, Any]
                ) -> float:
    """Active FLOPs of the prefills and decoded tokens in (t0, t1]."""
    flops = 0.0
    for rec in list(loop.live.values()) + loop.done:
        T = rec.prompt.size
        for j, t in enumerate(rec.times):
            if t0 < t <= t1:
                flops += (arith.prefill_flops(m, T) if j == 0
                          else arith.decode_token_flops(m, T + j))
    return flops


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float) -> Dict[str, Any]:
    """One run; returns what the result line needs."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.core import preset
    from repro.serve import PagedServeEngine, SamplingParams

    counter = CompileCounter()
    mix, m = cell.traffic, cell.model
    cfg = lm_config(cell.config)
    parts: Dict[str, float] = {}
    t = time.perf_counter()
    params = weights.make_params(seed, m)
    jax.block_until_ready(params)
    parts["weights_s"] = time.perf_counter() - t
    t = time.perf_counter()
    traffic = cell.generator().make(seed, mix, m["vocab"])
    engine = PagedServeEngine(params, cfg, preset(mix["precision"]),
                              **mix["engine"])
    del params
    jax.block_until_ready(engine.cache)
    parts["engine_s"] = time.perf_counter() - t

    t = time.perf_counter()
    for prompt in traffic.warm_prompts():
        engine.submit(prompt, SamplingParams(max_new_tokens=2))
    engine.drain()
    parts["compile_s"] = time.perf_counter() - t
    t = time.perf_counter()
    loop = Load(engine, traffic)
    loop.start()
    while not loop.filled() or time.perf_counter() - t < mix.get(
            "warm_s", 0.0):
        loop.step()
    parts["fill_s"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start

    seconds = window_s(seconds, trace)
    before = counter.count()
    with traced(trace) as tr:
        with TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                loop.step()
            t1 = loop.steps[-1][1]
    compiles = counter.count() - before
    nums = serve.window_numbers(loop, t0, t1, m)
    nums["model_flops"] = model_flops(loop, t0, t1, m)
    held = [r for (_, b, _), r in zip(loop.steps, loop.routing)
            if t0 < b <= t1 and r is not None]
    dropped = engine.stats()["moe.dropped"]
    slowest = serve.longest_step(loop.steps, t0, t1)
    mem = memory_peak(devices)
    print(f"bench: window {nums['tokens']} tokens and "
          f"{len(nums['finished'])} requests done in {t1 - t0:.3f} s, "
          f"memory peak {mem}", file=sys.stderr, flush=True)
    del engine, loop
    gc.collect()

    fin = nums["finished"]
    longest = max(range(len(fin)), key=lambda i: fin[i].prompt.size
                  + len(fin[i].tokens)) if fin else None
    picked = [fin[i] for i in sample_indices(
        seed, len(fin), mix["checked_requests"],
        [] if longest is None else [longest])]
    t = time.perf_counter()
    params = weights.make_params(seed, m)
    gap = greedy_gap(params, cell, picked)
    diag = {"reference_s": time.perf_counter() - t,
            **{k: v for k, v in gap.items() if k != "gap"},
            "checked_tokens": sum(len(r.tokens) for r in picked),
            "itl_ms_p50_p90_p95_p99": [1e3 * percentile(nums["itl_s"], q)
                                       for q in (50, 90, 95, 99)],
            "step_ms_p50_p90_p99": [1e3 * percentile(nums["step_s"], q)
                                    for q in (50, 90, 99)],
            "window_steps": len(nums["step_s"]),
            "window_first_tokens": len(nums["ttft_s"]),
            "longest_step_ms_at_s_rows": slowest,
            "held_tokens_per_expert_layer_mean": (
                sum(float(h.mean()) for h in held) / len(held)
                if held else None)}
    return {
        "setup_s": setup_s, "setup_parts": parts, "window_s": t1 - t0,
        "attempted": len(fin),
        "failed": sum(r.finish != "length" for r in fin),
        "memory_peak": mem, "trace": tr["trace"], "window": nums,
        "readings": {"greedy_gap": gap["gap"], "dropped_tokens": dropped,
                     "window_compiles": float(compiles)},
        "checked": picked, "tokens": nums["tokens"], "diagnostics": diag,
        "counts": {"chips": cell.chips, "held_tokens": held,
                   **{k: v for k, v in nums.items() if k != "finished"}},
    }


def _precision(cell: Cell) -> Precision:
    return Precision.of(cell.traffic.get("reference"))


# The percentile of the gaps read as ``greedy_gap`` (module docstring).
GAP_PERCENTILE = 95.0


def gap_stats(params, m, recs, max_len: int, pr: Precision, q: float,
              pick_pr: Optional[Precision] = None) -> Dict[str, Any]:
    """Over every served token of ``recs``, the gap between the
    reference's best logit and the logit of the token served, or, with
    ``pick_pr``, of the token that the reference in that precision puts
    first at the same position.  ``gap``: the gaps' ``q``-th percentile
    (100: the widest); beside it, for the diagnostics, their 50th, 90th,
    95th and 99th percentiles and the widest."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    if not recs:
        return {"gap": float("inf")}

    def logits_at(p, toks, pos, prec):
        h = reference.hidden(p, toks, m, pr=prec)
        return reference.logits(
            p, jnp.take_along_axis(h, pos[..., None], axis=1), prec)

    @jax.jit
    def block(p, toks, pos, chosen):
        lg = logits_at(p, toks, pos, pr)
        if pick_pr is not None:
            chosen = jnp.argmax(logits_at(p, toks, pos, pick_pr), axis=-1)
        got = jnp.take_along_axis(lg, chosen[..., None], -1)[..., 0]
        return jnp.max(lg, axis=-1) - got

    toks, pos, chosen, valid = serve._rows(recs, max_len)
    n = serve.REF_ROWS
    gaps = []
    for i in range(0, toks.shape[0], n):
        g = block(params, *(jnp.asarray(a[i:i + n])
                            for a in (toks, pos, chosen)))
        gaps.append(jax.device_get(g)[valid[i:i + n]])
    g = np.concatenate(gaps)
    return {"gap": float(np.percentile(g, q)),
            "gap_p50_p90_p95_p99_max": [float(np.percentile(g, x))
                                        for x in (50, 90, 95, 99, 100)]}


def greedy_gap(params, cell: Cell, recs) -> Dict[str, Any]:
    """``gap_stats`` of the served tokens, the reference in the precision
    the mix states; its ``gap`` is the reading ``greedy_gap``."""
    return gap_stats(params, cell.model, recs,
                     cell.traffic["engine"]["max_len"], _precision(cell),
                     GAP_PERCENTILE)


def control(cell: Cell, seed: int, seconds: float, devices,
            out: Dict[str, Any]) -> Dict[str, Any]:
    """The control's readings beside a sound run ``out`` of the same seed:
    the reference with its weights in the next format below, the limits
    file's ``control``, put in the program's place at the served
    positions, its first choices scored by the reference."""
    params = weights.make_params(seed, cell.model)
    pr = _precision(cell)
    low = dataclasses.replace(pr, weights=cell.limits["control"])
    st = gap_stats(params, cell.model, out["checked"],
                   cell.traffic["engine"]["max_len"], pr, GAP_PERCENTILE,
                   low)
    return {"greedy_gap": st.pop("gap"),
            **{"control_" + k: v for k, v in st.items()}}
