"""Roofline time of the window's decode-step projection GEMMs (weights
credited MX-packed) over the device time of the MX GEMM kernel inside the
paged decode step's program (profiler trace)."""
from bench.harness import arith

KERNEL, PROGRAM = "mx_matmul_pallas", "serve_step_paged"


def read(run):
    t, c = run.trace, run.counts
    if t is None:
        return None
    busy = t.op_s(lambda o: o.label == KERNEL and PROGRAM in o.module)
    if busy <= 0:
        return None
    ideal = sum(arith.decode_gemm_ideal_s(run.model, r, run.peak)
                for r in c["decode_rows"] if r > 0) / c["chips"]
    return 100.0 * ideal / busy
