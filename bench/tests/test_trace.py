"""Idle share, kernel time and the breakdown from a synthetic
trace, and the window and host spans from a trace recorded here."""
import pytest

from bench.harness.trace import Op, Trace, label, read, traced


def _trace():
    # chip 0: busy [0,2) [3,4) [4,6) [8,9); chip 1: busy [0,10)
    ops = {0: [Op(0, 2, "mx_matmul_pallas"), Op(3, 4, "all-gather-start"),
               Op(4, 6, "fusion"), Op(8, 9, "mx_attn_fwd_pallas"),
               Op(0, 9, "while")],
           1: [Op(0, 10, "all-reduce"), Op(2, 4, "fusion")]}
    spans = [(0, 10, "engine.step"), (6.5, 7.5, "client.submit")]
    return Trace(ops, spans, (0.0, 10.0))


def test_labels_of_tpu_op_names():
    assert label("%mx_matmul_pallas.115 = bf16[8192,2048]{1,0} custom-call("
                 "bf16[8192,512] %bitcast.537)") == "mx_matmul_pallas"
    assert label("%fusion.333 = (bf16[16,512]) fusion(...)") == "fusion"
    assert label("%all-gather-start.2 = (f32[8]) all-gather-start(x)") == \
        "all-gather-start"
    assert label("%while.11 = (s32[]) while(...)") == "while"
    assert label("%copy-start = (pred[1,256]) copy-start(x)") == \
        "copy-start"


def test_busy_idle_and_kernel_time():
    t = _trace()
    assert t.busy_s() == pytest.approx((9 + 10) / 2)
    assert t.op_s(lambda o: o.label == "mx_matmul_pallas") == \
        pytest.approx(1.0)


def test_breakdown():
    t = Trace({0: [o for o in _trace().ops[0] if o.label != "while"],
               1: _trace().ops[1]}, _trace().spans, (0.0, 10.0))
    gaps = t.idle_gaps()
    assert gaps[0] == ["client.submit", pytest.approx(2.0)]
    assert [g[1] for g in gaps] == pytest.approx([2.0, 1.0, 1.0])
    assert gaps[1][0] == "engine.step"
    top = _trace().top_ops()
    assert top[0] == ["all-reduce", pytest.approx(5.0)]
    assert "while" not in [n for n, _ in top] and len(top) <= 10


def test_window_and_spans_from_a_recorded_trace():
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with traced(True) as out:
        with TraceAnnotation("bench.window"):
            with TraceAnnotation("engine.step"):
                f(x).block_until_ready()
    t = out["trace"]
    assert t.window_s > 0
    assert any(name == "engine.step" for _, _, name in t.spans)
    assert t.host_activity(sum(t.window) / 2) == "engine.step"


def test_read_needs_the_window(tmp_path):
    with pytest.raises(FileNotFoundError):
        read(str(tmp_path))


def test_idle_share():
    """Chip 0 is idle over [9,10) of a 10-s window (its ``while`` spans
    [0,9)), chip 1 never: 0.5 s of 10 on the average."""
    from bench.harness import spec
    from conftest import ROOT
    read = spec.metric_reader("device_idle_pct.serve", ROOT)

    class Run:
        trace = _trace()
    assert read(Run) == pytest.approx(100 * 0.5 / 10.0)
    Run.trace = Trace({}, [], (0.0, 10.0))
    assert read(Run) is None
