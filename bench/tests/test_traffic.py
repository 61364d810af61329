"""The generators repeat from a seed, keep to their clips, and give every
seed the same set of sizes in another order, spread evenly."""
import json

import numpy as np
import pytest

from conftest import ROOT

from bench.harness import spec
from bench.harness.lengths import (BLOCK, GRID, STRATA, length_grid,
                                   residual_grid)

MIX = json.loads((ROOT / "bench/traffic/chat-closed64.json").read_text())
CHAT = spec.load("generators", "chat", ROOT)


def test_same_seed_same_requests():
    a, b = CHAT.make(2**40 + 3, MIX, 49152), CHAT.make(2**40 + 3, MIX, 49152)
    for i in (0, 5, 70, GRID + 7):
        pa, oa = a.request(i)
        pb, ob = b.request(i)
        assert oa == ob and np.array_equal(pa, pb)


def test_lengths_respect_clips_and_medians():
    t = CHAT.make(1, MIX, 49152)
    lens = [t.lengths(i) for i in range(GRID, 2 * GRID)]
    p, o = np.array(lens).T
    assert p.min() >= 64 and p.max() <= 1536 and o.min() >= 16
    assert o.max() <= 512
    assert abs(np.median(p) - 512) <= 8 and abs(np.median(o) - 128) <= 4
    prompt, _ = t.request(3)
    assert prompt.dtype == np.int32 and prompt.max() < 49152


def test_first_requests_take_the_residual_lengths():
    """The closed loop starts as it would stand in steady state: the
    clients' first replies are what is left of replies met mid-life,
    the same set for every seed, in a seed-drawn order."""
    a, b = CHAT.make(1, MIX, 100), CHAT.make(2**35, MIX, 100)
    n = MIX["arrivals"]["clients"]
    fa = [a.lengths(i)[1] for i in range(n)]
    fb = [b.lengths(i)[1] for i in range(n)]
    assert fa != fb and sorted(fa) == sorted(fb)
    assert min(fa) >= 1 and max(fa) <= 512
    grid = length_grid(MIX["output_len"])
    # a renewal process meets a reply mid-life: the mean of what is left is
    # E[L^2] / (2 E[L]) (+ 1/2 for whole tokens)
    expect = (grid.astype(float) ** 2).mean() / (2 * grid.mean()) + 0.5
    assert np.mean(residual_grid(grid, 1024)) == pytest.approx(expect,
                                                               rel=0.02)


def test_every_seed_gets_the_same_work_in_its_own_order():
    a, b = CHAT.make(1, MIX, 100), CHAT.make(2**35, MIX, 100)
    la = [a.lengths(i) for i in range(GRID, 3 * GRID)]
    lb = [b.lengths(i) for i in range(GRID, 3 * GRID)]
    assert la != lb
    for e in range(2):
        for k in (0, 1):
            ea = sorted(x[k] for x in la[e * GRID:(e + 1) * GRID])
            eb = sorted(x[k] for x in lb[e * GRID:(e + 1) * GRID])
            assert ea == eb
    assert not np.array_equal(a.request(0)[0], b.request(0)[0])


def test_every_block_holds_the_same_lengths_for_every_seed():
    """A window that sees whole blocks sees the same work for any seed."""
    a, b = CHAT.make(3, MIX, 100), CHAT.make(2**41 + 5, MIX, 100)
    for blk in range(BLOCK + 3):
        la = [a.lengths(blk * STRATA + j)[0] for j in range(STRATA)]
        lb = [b.lengths(blk * STRATA + j)[0] for j in range(STRATA)]
        assert sorted(la) == sorted(lb)


def test_every_block_holds_one_length_of_each_stratum():
    t = CHAT.make(2**40 + 9, MIX, 100)
    prompts = np.sort(length_grid(MIX["prompt_len"]))
    for blk in range(3 * BLOCK):
        got = sorted(t.lengths(blk * STRATA + j)[0] for j in range(STRATA))
        for s, x in enumerate(got):
            lo, hi = prompts[s * BLOCK], prompts[(s + 1) * BLOCK - 1]
            assert lo <= x <= hi


def test_requests_that_cannot_fit_the_engine_are_refused():
    mix = dict(MIX, prompt_len=dict(MIX["prompt_len"], max=2000))
    with pytest.raises(ValueError):
        CHAT.make(1, mix, 100)


def test_length_grid_is_the_distribution():
    g = length_grid({"dist": "lognormal", "median": 100, "sigma": 0.5,
                     "min": 1, "max": 10**6}, 1001)
    assert g[500] == 100 and g[0] < 100 < g[-1]


def test_an_added_generator_of_poisson_sessions(tiny_root):
    """A generator added as a file: open-loop Poisson arrivals of
    requests from sessions that share a prefix."""
    c = spec.resolve("tiny.sessions", tiny_root)
    gen = c.generator()
    t = gen.make(5, c.traffic, 1000)
    times = [t.arrival(i) for i in range(2 * GRID)]
    assert times[0] == 0.0 and all(b > a for a, b in zip(times, times[1:]))
    rate = c.traffic["arrivals"]["rate"]
    assert np.mean(np.diff(times[:GRID + 1])) == pytest.approx(1 / rate,
                                                               rel=0.05)
    p0, p1, p2 = (t.request(i)[0] for i in range(3))
    n = c.traffic["sessions"]["prefix_len"]
    assert np.array_equal(p0[:n], p2[:n]) and not np.array_equal(p0[:n],
                                                                 p1[:n])
    again = gen.make(5, c.traffic, 1000)
    assert again.arrival(40) == t.arrival(40)
    assert np.array_equal(again.request(9)[0], t.request(9)[0])
