"""The serving driver: the program's ``PagedServeEngine`` on seeded bf16
weights, driven by the mix's clients on the benchmark's own clock.

The mix's generator (``bench/generators/<generator>.py``) gives the
requests: a closed loop keeps ``clients`` requests in flight, each client
sending its next request when its reply completes; where the generator
gives arrival times, an open loop sends each request at its time from the
start of the load, whether or not the engine has caught up.  Set-up runs
the generator's warm-up prompts through the engine (every shape the
traffic reaches), then the fill: the load starts, and the window opens
once every request sent at the start has its first token (and, where the
mix sets ``warm_s``, that many seconds of load have passed).

A request's time to first token runs from the moment its client sent it
(its arrival time, in an open loop) to the end of the ``engine.step()``
after which its first token was on the host; its inter-token gaps are the
times between the steps that returned its tokens.  After the window the
reference scores a sample of the finished requests, the longest among
them: for each served token, how far its logit lies below the
reference's best at that position.
"""
from __future__ import annotations

import dataclasses
import gc
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

from bench.harness import arith, reference, weights
from bench.harness.common import CompileCounter, memory_peak, percentile
from bench.harness.lengths import sample_indices
from bench.harness.spec import Cell, lm_config
from bench.harness.trace import traced, window_s


@dataclasses.dataclass
class Rec:
    index: int
    prompt: np.ndarray
    out_len: int
    submit_t: float
    req: Any
    times: List[float] = dataclasses.field(default_factory=list)
    done_t: Optional[float] = None
    finish: Optional[str] = None
    tokens: Optional[List[int]] = None


class Load:
    """The mix's clients: a closed loop of ``traffic.clients`` clients, or,
    where the generator gives arrival times, an open loop."""

    def __init__(self, engine, traffic):
        self.engine = engine
        self.traffic = traffic
        self.next = 0
        self.t_load = 0.0
        self.first = 0
        self.live: Dict[int, Rec] = {}
        self.done: List[Rec] = []
        self.steps: List[tuple] = []        # (t_begin, t_end, decode rows)

    @property
    def closed(self) -> bool:
        return self.traffic.arrival(0) is None

    def start(self) -> None:
        self.t_load = time.perf_counter()
        if self.closed:
            for _ in range(self.traffic.clients):
                self.submit()
        self.first = self.next

    def _due(self) -> float:
        return self.t_load + self.traffic.arrival(self.next)

    def arrive(self) -> None:
        """Open loop: send every request whose time has come; wait for the
        next one when nothing is in flight."""
        from jax.profiler import TraceAnnotation
        if not self.live:
            with TraceAnnotation("client.wait"):
                time.sleep(max(0.0, self._due() - time.perf_counter()))
        now = time.perf_counter()
        while self._due() <= now:
            self.submit(self._due())

    def submit(self, sent: Optional[float] = None) -> None:
        from jax.profiler import TraceAnnotation

        from repro.serve import SamplingParams
        prompt, out_len = self.traffic.request(self.next)
        with TraceAnnotation("client.submit"):
            t = time.perf_counter()
            rid = self.engine.submit(prompt, SamplingParams(
                temperature=0.0, max_new_tokens=out_len))
        req = self.engine.sched.queue[-1]
        if req.rid != rid:
            raise RuntimeError(f"submitted {rid}, queue ends in {req.rid}")
        self.live[rid] = Rec(self.next, prompt, out_len,
                             t if sent is None else sent, req)
        self.next += 1

    def filled(self) -> bool:
        """Every request that ``start`` sent has its first token."""
        return all(rec.times for rec in self.live.values()
                   if rec.index < self.first)

    def step(self) -> None:
        from jax.profiler import TraceAnnotation
        if not self.closed:
            self.arrive()
        with TraceAnnotation("engine.step"):
            t0 = time.perf_counter()
            finished = self.engine.step()
            t1 = time.perf_counter()
        with TraceAnnotation("client.observe"):
            rows = 0
            for rec in self.live.values():
                n = len(rec.req.tokens)
                rows += (n > len(rec.times)) and n > 1
                rec.times.extend([t1] * (n - len(rec.times)))
            self.steps.append((t0, t1, rows))
            for req in finished:
                rec = self.live.pop(req.rid)
                rec.done_t, rec.finish = t1, req.finish_reason
                rec.tokens = [int(x) for x in req.tokens]
                self.done.append(rec)
                if self.closed:
                    self.submit()


def longest_step(steps, t0: float, t1: float) -> List[float]:
    """The window's longest ``engine.step()``: its milliseconds, its start
    in seconds from the window's, and the rows it decoded."""
    inside = [(b - a, a - t0, r) for a, b, r in steps if t0 < b <= t1]
    if not inside:
        return []
    d, at, rows = max(inside)
    return [1e3 * d, at, rows]


def window_numbers(loop: Load, t0: float, t1: float,
                   m: Dict[str, Any]) -> Dict[str, Any]:
    """Tokens, time to first token, inter-token gaps, engine step times
    and model FLOPs of everything that happened in (t0, t1]."""
    ttft, itl, tokens, flops = [], [], 0, 0.0
    for rec in list(loop.live.values()) + loop.done:
        T = rec.prompt.size
        for j, t in enumerate(rec.times):
            if not t0 < t <= t1:
                continue
            tokens += 1
            if j == 0:
                ttft.append(t - rec.submit_t)
                flops += arith.prefill_flops(m, T)
            else:
                itl.append(t - rec.times[j - 1])
                flops += arith.decode_token_flops(m, T + j)
    steps = [(a, b, r) for a, b, r in loop.steps if t0 < b <= t1]
    return {"tokens": tokens, "ttft_s": ttft, "itl_s": itl,
            "step_s": [b - a for a, b, _ in steps],
            "decode_rows": [r for _, _, r in steps],
            "model_flops": flops,
            "finished": [r for r in loop.done if t0 < r.done_t <= t1]}


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices,
        t_start: float) -> Dict[str, Any]:
    """One run; returns what the result line needs."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from repro.core import preset
    from repro.serve import PagedServeEngine, SamplingParams

    counter = CompileCounter()
    mix, m = cell.traffic, cell.model
    parts: Dict[str, float] = {}
    t = time.perf_counter()
    params = weights.make_params(seed, m, jnp.bfloat16)
    jax.block_until_ready(params)
    parts["weights_s"] = time.perf_counter() - t
    t = time.perf_counter()
    traffic = cell.generator().make(seed, mix, m["vocab"])
    engine = PagedServeEngine(params, lm_config(cell.config),
                              preset(mix["precision"]),
                              **mix["engine"])
    jax.block_until_ready(engine.cache)
    parts["engine_s"] = time.perf_counter() - t

    t = time.perf_counter()
    for prompt in traffic.warm_prompts():
        engine.submit(prompt, SamplingParams(max_new_tokens=2))
    engine.drain()
    parts["compile_s"] = time.perf_counter() - t
    # the fill: the closed loop's first requests are sent and prefilled,
    # and the window opens once every request sent has its first token
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
    nums = window_numbers(loop, t0, t1, m)
    slowest = longest_step(loop.steps, t0, t1)
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
    gap = greedy_gap(params, cell, picked)
    diag = {"reference_s": time.perf_counter() - t,
            "checked_tokens": sum(len(r.tokens) for r in picked),
            "itl_ms_p50_p90_p95_p99": [1e3 * percentile(nums["itl_s"], q)
                                       for q in (50, 90, 95, 99)],
            "step_ms_p50_p90_p99": [1e3 * percentile(nums["step_s"], q)
                                    for q in (50, 90, 99)],
            "window_steps": len(nums["step_s"]),
            "window_first_tokens": len(nums["ttft_s"]),
            "longest_step_ms_at_s_rows": slowest}
    return {
        "setup_s": setup_s, "setup_parts": parts, "window_s": t1 - t0,
        "attempted": len(fin),
        "failed": sum(r.finish != "length" for r in fin),
        "memory_peak": mem, "trace": tr["trace"], "window": nums,
        "readings": {"greedy_gap": gap,
                     "window_compiles": float(compiles)},
        "checked": picked, "tokens": nums["tokens"], "diagnostics": diag,
        "counts": {"chips": cell.chips, **{k: v for k, v in nums.items()
                                           if k != "finished"}},
    }


# Rows of one reference call: the reference runs over the checked requests
# in blocks of this many, each padded to the same shape, so that it fits
# beside nothing but the weights and compiles once.
REF_ROWS = 4


def _rows(recs: List[Rec], max_len: int):
    """Teacher-forced inputs (prompt, then each served token but the
    last), the position each served token was chosen at, and the token,
    in blocks of ``REF_ROWS`` rows; served tokens padded to a power of
    two."""
    n = -(-len(recs) // REF_ROWS) * REF_ROWS
    out_max = max(len(r.tokens) for r in recs)
    P = 1 << (out_max - 1).bit_length()
    toks = np.zeros((n, max_len), np.int32)
    pos = np.zeros((n, P), np.int32)
    chosen = np.zeros((n, P), np.int32)
    valid = np.zeros((n, P), bool)
    for i, r in enumerate(recs):
        T, out = r.prompt.size, r.tokens
        seq = np.concatenate([r.prompt, np.asarray(out[:-1], np.int32)])
        toks[i, :seq.size] = seq
        pos[i, :len(out)] = T - 1 + np.arange(len(out))
        chosen[i, :len(out)] = out
        valid[i, :len(out)] = True
    return toks, pos, chosen, valid


def _precision(cell: Cell):
    return reference.Precision.of(cell.traffic.get("reference"))


def _gaps(params, m, recs: List[Rec], max_len: int, pr,
          pick_pr=None) -> float:
    """Widest gap, over every served token of ``recs``, between the
    reference's best logit and the logit of the token served, or, with
    ``pick_pr``, of the token that the reference in that precision puts
    first at the same position."""
    import jax
    import jax.numpy as jnp
    if not recs:
        return float("inf")

    def logits_at(p, toks, pos, prec):
        h = reference.hidden(p, toks, m, pr=prec)
        hs = jnp.take_along_axis(h, pos[..., None], axis=1)
        return reference.logits(p, hs, prec)

    @jax.jit
    def block_gap(p, toks, pos, chosen, valid):
        lg = logits_at(p, toks, pos, pr)
        if pick_pr is not None:
            chosen = jnp.argmax(logits_at(p, toks, pos, pick_pr), axis=-1)
        got = jnp.take_along_axis(lg, chosen[..., None], -1)[..., 0]
        return jnp.max(jnp.where(valid, jnp.max(lg, axis=-1) - got,
                                 -jnp.inf))

    rows = _rows(recs, max_len)
    return max(float(block_gap(params, *(jnp.asarray(a[i:i + REF_ROWS])
                                         for a in rows)))
               for i in range(0, rows[0].shape[0], REF_ROWS))


def greedy_gap(params, cell: Cell, recs: List[Rec]) -> float:
    """The served tokens' widest gap below the reference's best, the
    reference in the precision the mix states."""
    return _gaps(params, cell.model, recs, cell.traffic["engine"]["max_len"],
                 _precision(cell))


def control(cell: Cell, seed: int, seconds: float, devices,
            out: Dict[str, Any]) -> Dict[str, float]:
    """The control's readings beside a sound run ``out`` of the same seed:
    the reference with its weights in the next format below, the limits
    file's ``control``, put in the program's place at the served
    positions, its first choices scored by the reference."""
    import jax.numpy as jnp
    params = weights.make_params(seed, cell.model, jnp.bfloat16)
    mix, pr = cell.traffic, _precision(cell)
    low = dataclasses.replace(pr, weights=cell.limits["control"])
    return {"greedy_gap": _gaps(params, cell.model, out["checked"],
                                mix["engine"]["max_len"], pr, low)}
