"""Roofline time of the window's decode-step GEMMs of the experts held
here, over the device time of the expert kernel inside the paged decode
step's program (profiler trace).  The roofline counts, from the program's
routing counters, only the experts a step routed a token to, each of its
three GEMMs at 2 * tokens * K * N operations with its weights MX-packed
(``harness/arith_deepseek_v3.py``).  A program without the counters or
the kernel's label gives nothing to read."""
from bench.harness import arith_deepseek_v3 as arith

KERNEL, PROGRAM = "mx_expert_matmul_pallas", "serve_step_paged"


def read(run):
    t, c = run.trace, run.counts
    held = c.get("held_tokens")
    if t is None or not held:
        return None
    busy = t.op_s(lambda o: o.label == KERNEL and PROGRAM in o.module)
    if busy <= 0:
        return None
    ideal = sum(arith.expert_gemm_ideal_s(run.model, h, run.peak)
                for h in held) / c["chips"]
    return 100.0 * ideal / busy
