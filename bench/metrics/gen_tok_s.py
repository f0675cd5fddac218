"""Generated tokens that reached the host in the window, per second of the
window (host clock)."""


def read(run):
    return run["tokens"] / run["window_s"] if run["window_s"] > 0 else None
