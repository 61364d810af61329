"""The split of the time to first token at the program's own stamps: the
queue up to the first prefill chunk's dispatch, then the chunks."""
import time
from types import SimpleNamespace

import jax
import pytest

from conftest import CPU_PEAK, ROOT

from bench.harness import spec
from bench.harness.execute import execute, read_metrics

SPLIT = ("ttft_queue_p95_ms.serve", "ttft_prefill_p95_ms.serve")


def _out(reqs):
    return {"setup_s": 1.0, "window_s": 2.0,
            "window": {"tokens": 0, "ttft_s": [], "itl_s": [],
                       "finished": [SimpleNamespace(req=r) for r in reqs]}}


def _split_metrics(cell):
    return [m for m in cell.per_layer if m["name"] in SPLIT]


def test_split_reads_the_program_stamps():
    cell = spec.resolve("starcoder2-3b.serve-chat", ROOT)
    reqs = [SimpleNamespace(submit_t=10.0, prefill_t=10.0 + q,
                            first_token_t=10.0 + q + p)
            for q, p in [(0.5, 0.25)] * 18 + [(8.0, 2.0)] * 2]
    m = read_metrics(cell, _split_metrics(cell), _out(reqs), {})
    assert m["ttft_queue_p95_ms.serve"]["value"] == pytest.approx(8000.0)
    assert m["ttft_prefill_p95_ms.serve"]["value"] == pytest.approx(2000.0)


def test_a_program_without_the_stamp_leaves_the_split_out():
    """Requests with no ``prefill_t`` (a program before the stamp) and a
    window in which nothing finished give nothing to read."""
    cell = spec.resolve("starcoder2-3b.serve-chat", ROOT)
    old = [SimpleNamespace(submit_t=1.0, first_token_t=2.0)] * 3
    assert _split_metrics(cell)
    for reqs in (old, []):
        assert read_metrics(cell, _split_metrics(cell), _out(reqs), {}) == {}


def test_split_from_a_traced_tiny_run(tiny_root):
    """Each finished request's stamps lie between its client's send and
    the end of the step that brought its first token to the benchmark."""
    c = spec.resolve("tiny.serve", tiny_root)
    res = execute(c, 2**33 + 5, 0.5, True, jax.devices()[:1], CPU_PEAK,
                  time.perf_counter())
    fin = res["out"]["window"]["finished"]
    assert fin
    for rec in fin:
        r = rec.req
        assert (rec.submit_t <= r.submit_t <= r.prefill_t
                <= r.first_token_t <= rec.times[0])
    for name in SPLIT:
        assert res["metrics"][name]["value"] >= 0.0
