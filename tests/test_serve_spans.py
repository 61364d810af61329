"""The serving engine's phase spans and request stamps.

A tiny paged engine with chunked prefill drains a few requests under a
CPU profiler trace: each ``step()`` is one ``serve.step`` span with its
phases inside it, the ``.wait`` phases are where the host blocks on the
device, ``serve.decode`` counts its live and dead rows, and the per-step
phase times, the request stamps and ``stats()`` agree with each other.
"""
import glob
import os
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.configs import get_config
from repro.core import preset
from repro.models import lm_init
from repro.serve import PagedServeEngine, SamplingParams, ServeEngine

MAX_BATCH = 3
LENGTHS = (70, 5, 40, 33)          # 70 tokens: three 32-token chunks


class _Blocked:
    """A device result whose read by the host is marked by a
    ``probe.block`` span."""

    def __init__(self, arr):
        self.arr = arr

    def __array__(self, dtype=None, copy=None):
        with TraceAnnotation("probe.block"):
            return np.asarray(self.arr, dtype)

    def __getitem__(self, i):
        with TraceAnnotation("probe.block"):
            return np.asarray(self.arr)[i]


def _probed(base):
    class Probed(base):
        def _decode_batch(self, *a, **kw):
            return _Blocked(super()._decode_batch(*a, **kw))

        def _first_token(self, logits, sp):
            return _Blocked(super()._first_token(logits, sp))
    return Probed


def _engine(kind="paged", **kw):
    cfg = get_config("qwen2-7b", "smoke")
    params = lm_init(jax.random.PRNGKey(0), cfg)
    qcfg = preset("e4m3_bf16act")
    if kind == "paged":
        return _probed(PagedServeEngine)(
            params, cfg, qcfg, max_batch=MAX_BATCH, max_len=128,
            page_size=32, chunk_size=32, **{"n_pages": 16, **kw})
    return _probed(ServeEngine)(params, cfg, qcfg, max_batch=MAX_BATCH,
                                max_len=128, bucket_prompts=False)


def _submit(eng, seed, max_new=4):
    rng = np.random.RandomState(seed)
    for n in LENGTHS:
        eng.submit(rng.randint(1, 200, size=n),
                   SamplingParams(max_new_tokens=max_new))


def _host_events(path):
    """(start_ns, end_ns, name, stats) of the host's ``serve.*``,
    ``probe.*`` and ``caller.*`` events, in start order."""
    (f,) = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(f).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("serve.", "probe.", "caller.")):
                    out.append((ev.start_ns, ev.end_ns, ev.name,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: (e[0], -e[1]))


@pytest.fixture(scope="module", params=["paged", "slab"])
def drained(request, tmp_path_factory):
    """An engine warmed on one drain, then a second drain stepped by hand
    under the profiler, each step inside a caller's ``caller.step`` span:
    its events, each step's wall time and phases."""
    eng = _engine(request.param)
    _submit(eng, 0)
    eng.drain()
    path = str(tmp_path_factory.mktemp("trace"))
    _submit(eng, 1)
    steps = []
    jax.profiler.start_trace(path)
    try:
        while eng.has_work:
            with TraceAnnotation("caller.step"):
                t0 = time.perf_counter()
                eng.step()
            steps.append((time.perf_counter() - t0,
                          dict(eng.spans.last_step)))
    finally:
        jax.profiler.stop_trace()
    return request.param, eng, steps, _host_events(path)


def test_each_step_is_one_serve_step_with_its_phases_inside(drained):
    _, _, steps, events = drained
    roots = [e for e in events if e[2] == "serve.step"]
    assert len(roots) == len(steps)
    for s, e, name, _ in events:
        if name in ("serve.step", "caller.step"):
            continue
        inside = [r for r in roots if r[0] <= s and e <= r[1]]
        assert len(inside) == 1, name
    phases = {n for _, _, n, _ in events if n.startswith("serve.")}
    assert {"serve.admit", "serve.prefill", "serve.prefill.wait",
            "serve.place", "serve.pages", "serve.decode",
            "serve.decode.wait", "serve.finish"} <= phases


def test_steps_lie_inside_the_callers_span_on_one_clock(drained):
    """A caller's own span around ``step()`` (as a benchmark keeps one)
    holds exactly one ``serve.step``: both are on the profiler's host
    clock, so a reader can name a moment by the engine's phase."""
    _, _, steps, events = drained
    callers = [e for e in events if e[2] == "caller.step"]
    roots = [e for e in events if e[2] == "serve.step"]
    assert len(callers) == len(roots) == len(steps)
    for (cs, ce, _, _), (rs, re_, _, _) in zip(callers, roots):
        assert cs <= rs and re_ <= ce


def test_phases_do_not_overlap_within_a_step(drained):
    _, _, _, events = drained
    phases = [e for e in events
              if e[2].startswith("serve.") and e[2] != "serve.step"]
    for a, b in zip(phases, phases[1:]):
        assert a[1] <= b[0], (a[2], b[2])


def test_wait_spans_are_where_the_host_blocks(drained):
    _, _, _, events = drained
    waits = [e for e in events if e[2].endswith(".wait")]
    assert {e[2] for e in waits} == {"serve.prefill.wait",
                                     "serve.decode.wait"}
    blocks = [e for e in events if e[2] == "probe.block"]
    assert blocks
    for s, e, _, _ in blocks:
        assert any(w[0] <= s and e <= w[1] for w in waits)
    # every wait holds a block: nothing else is called a wait
    for w in waits:
        assert any(w[0] <= b[0] and b[1] <= w[1] for b in blocks), w[2]


def test_decode_counts_live_and_dead_rows(drained):
    kind, _, _, events = drained
    decodes = [st for _, _, n, st in events if n == "serve.decode"]
    assert decodes
    for st in decodes:
        assert set(st) == {"live", "dead"}
        assert st["live"] + st["dead"] == MAX_BATCH and st["live"] >= 1
    assert any(st["dead"] for st in decodes)
    prefills = [st for _, _, n, st in events if n == "serve.prefill"]
    assert sorted(st["tokens"] for st in prefills) == (
        sorted([32, 32, 6, 5, 32, 8, 32, 1]) if kind == "paged"
        else sorted(LENGTHS))


def test_step_phases_sum_to_no_more_than_its_wall_time(drained):
    _, _, steps, _ = drained
    for wall, phases in steps:
        assert phases["step"] <= wall
        assert sum(v for k, v in phases.items() if k != "step") <= \
            phases["step"]


def test_request_stamps_and_prefill_record(drained):
    _, eng, _, _ = drained
    done = list(eng.finished.values())
    assert len(done) == 2 * len(LENGTHS)
    for r in done:
        assert r.submit_t <= r.prefill_t <= r.first_token_t
    recs = [e for e in eng.events if e["event"] == "prefill"]
    assert len(recs) == len(done)
    for rec in recs:
        r = eng.finished[rec["rid"]]
        assert rec["queue_s"] == r.prefill_t - r.submit_t
        assert rec["ttft_s"] == r.first_token_t - r.submit_t
        assert 0 < rec["time_s"] <= rec["ttft_s"]


def test_decode_time_is_the_summed_decode_phases():
    eng = _engine()
    _submit(eng, 2)
    decode = 0.0
    while eng.has_work:
        eng.step()
        last = eng.spans.last_step
        decode += last.get("decode", 0.0) + last.get("decode.wait", 0.0)
    s = eng.stats()
    assert s["decode_steps"] > 0
    assert s["decode_time_s"] == pytest.approx(decode, rel=1e-12)
    assert s["prefill_time_s"] == pytest.approx(sum(
        e["time_s"] for e in eng.events if e["event"] == "prefill"))


def test_preemption_resets_prefill_t():
    """Three 40-token prompts decoding 40 tokens each outgrow a 6-page
    pool: the newest is preempted, loses its stamps and is stamped again
    when its prefill restarts."""
    preempted = {}

    class Engine(PagedServeEngine):
        def _preempt(self, exclude):
            ok = super()._preempt(exclude)
            if ok:
                req = self.sched.queue[0]
                assert req.prefill_t is None and req.first_token_t is None
                preempted.setdefault(req.rid, time.perf_counter())
            return ok

    cfg = get_config("qwen2-7b", "smoke")
    eng = Engine(lm_init(jax.random.PRNGKey(0), cfg), cfg,
                 preset("e4m3_bf16act"), max_batch=3, max_len=128,
                 n_pages=6, page_size=32)
    rng = np.random.RandomState(31)
    for _ in range(3):
        eng.submit(rng.randint(1, cfg.vocab, size=40),
                   SamplingParams(max_new_tokens=40))
    eng.drain()
    assert preempted
    for rid, t in preempted.items():
        r = eng.finished[rid]
        assert t < r.prefill_t <= r.first_token_t
        last = [e for e in eng.events
                if e["event"] == "prefill" and e["rid"] == rid][-1]
        assert last["queue_s"] == r.prefill_t - r.submit_t
