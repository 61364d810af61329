"""Median over every gap between consecutive tokens of every request, the
later token in the window (host clock): the gap a reader of a streamed
reply sees most.  At capacity the tail gaps are steps that also carry one
or two prefill chunks, and which of those a high percentile lands on
changes with the seed, so the tails are per-layer metrics."""
from bench.harness.common import percentile


def read(run):
    itl = run.out["window"]["itl_s"]
    return 1e3 * percentile(itl, 50) if itl else None
