"""``correct`` comes out false when the timed path is broken underneath,
and for the control, and true when neither is.  These skip the look for a
chip and drive the rest of a run on the CPU, on the tiny cells of
conftest.py."""
import contextlib
import time

import jax
import numpy as np
import pytest

from conftest import CPU_PEAK

from bench import control
from bench.harness import spec
from bench.harness.execute import execute

SEED = 2**32 + 17


def _run(root, cell, trace=False):
    c = spec.resolve(cell, root)
    return execute(c, SEED, 0.5, trace, jax.devices()[:1], CPU_PEAK,
                   time.perf_counter())


@contextlib.contextmanager
def altered_token():
    """Every decoded token is replaced by the next id where it is made."""
    from repro.serve import PagedServeEngine
    real = PagedServeEngine._decode_batch

    def altered(self, *a, **kw):
        nxt = np.asarray(real(self, *a, **kw)).copy()
        nxt[:] = (nxt + 1) % self.cfg.vocab
        return nxt
    PagedServeEngine._decode_batch = altered
    try:
        yield
    finally:
        PagedServeEngine._decode_batch = real


@pytest.mark.parametrize("cell", ["tiny.serve", "tiny.sessions"])
def test_sound_serving_is_correct(tiny_root, cell):
    res = _run(tiny_root, cell)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0


@pytest.mark.parametrize("cell", ["tiny.serve", "tiny.sessions"])
def test_altered_token_is_not(tiny_root, cell):
    with altered_token():
        res = _run(tiny_root, cell)
    assert not res["correct"]


def test_serving_control_is_not(tiny_root):
    """The control, judged by the cell's limits as ``control.py`` judges it
    on the chip, comes out not correct beside a sound run."""
    c = spec.resolve("tiny.serve", tiny_root)
    res = _run(tiny_root, "tiny.serve")
    low = dict(res["out"]["readings"], **c.driver().control(
        c, SEED, 0.5, jax.devices()[:1], res["out"]))
    assert control.correct(res["out"]["readings"], c.limits)
    assert not control.correct(low, c.limits), low


def test_a_cell_added_by_files_reads_its_own_metric(tiny_root):
    """The open-loop sessions cell, its generator and its metric all added
    as files, runs traced on the CPU and reports that metric."""
    res = _run(tiny_root, "tiny.sessions", trace=True)
    assert res["correct"], res["compared"]
    assert res["metrics"]["requests_done.serve"]["value"] >= 1
    assert "breakdown" in res and res["device"]["window_s"] > 0
