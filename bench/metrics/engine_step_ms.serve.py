"""Median host wall time of one ``engine.step()`` call in the window, from
the benchmark's own span around the call."""
import statistics


def read(run):
    steps = run.counts["step_s"]
    if not steps:
        return None
    return 1e3 * statistics.median(steps)
