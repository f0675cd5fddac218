"""Bytes the decode steps of the traced slice need (the family's
`step_work`: weights, the KV cache of the real context, the new K/V and
the logits) over the device time of those steps' programs, as a share of
the chip's HBM bandwidth, in percent."""


def read(run):
    t = run["trace"]
    if not t or not t["steps_matched"]:
        return None
    secs = t["module_s"].get("decode", 0.0)
    dec = [s for s in run["steps"] if s["kind"] == "decode"]
    if not secs or not dec:
        return None
    need = sum(run["family"].step_work(run["dims"], s)[1] for s in dec)
    return 100.0 * need / secs / run["peak"]["hbm_bytes_per_s"]
