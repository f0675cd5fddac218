"""Share of the traced slice in which no operation ran on the device: 1
minus the union of op intervals over the slice, in percent."""


def read(run):
    t = run["trace"]
    if not t or not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
