"""99th percentile over every gap between consecutive tokens of every
request, the later token in the window (host clock)."""
from bench.harness.common import percentile


def read(run):
    itl = run.out["window"]["itl_s"]
    return 1e3 * percentile(itl, 99) if itl else None
