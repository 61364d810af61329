"""Requests finished in the window: the example of a metric that a cell
adds as a file of its own (the tests copy it to ``bench/metrics/``)."""


def read(run):
    return float(len(run.out["window"]["finished"])) or None
