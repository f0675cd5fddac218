"""Process start to window start, less the TPU runtime's own start (the
first `jax.devices()`): imports, weights, plan warm-up, compile and the
warm-up batch (host clock)."""


def read(run):
    return run["setup_s"]
