"""Process start to the first timed step: weights and inputs from the
seed, compilation or cache load, and warm-up (host clock)."""


def read(run):
    return run.out["setup_s"]
