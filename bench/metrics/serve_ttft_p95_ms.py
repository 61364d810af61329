"""95th percentile over every request whose first token reached the host
in the window of the time from its client's send to that token (host
clock)."""
from bench.harness.common import percentile


def read(run):
    ttft = run.out["window"]["ttft_s"]
    return 1e3 * percentile(ttft, 95) if ttft else None
