"""Output tokens that reached the host in the window over the window's
wall time (host clock)."""


def read(run):
    if run.window_s <= 0:
        return None
    return run.out["window"]["tokens"] / run.window_s
