"""A family that exists only in the tests: the dense decoder's, found by
file name in this directory, with a shape object of its own (so that code
which took it for `bench.flops.Dims` would fail; a dataclass, as a family's
own shape object would be) and a log of the contract calls the harness and
the metrics make (`CALLS`)."""
from __future__ import annotations

import dataclasses

from bench import harness

DENSE = harness.load_family("dense_decoder")
CALLS = []


@dataclasses.dataclass(frozen=True)
class Dims:
    dense: object


def _log(name):
    CALLS.append(name)
    return getattr(DENSE, name)


def program_config(config):
    return _log("program_config")(config)


def dims(config):
    return Dims(_log("dims")(config))


def make_weights(config, seed, sharding=None):
    return _log("make_weights")(config, seed, sharding)


def to_program(w, config):
    return _log("to_program")(w, config)


def reference_gaps(w, config, tokens, start, served, n_out, control):
    return _log("reference_gaps")(w, config, tokens, start, served, n_out,
                                  control)


def step_work(dims, step):
    return _log("step_work")(dims.dense, step)


def step_gemms(dims, step):
    return _log("step_gemms")(dims.dense, step)


def attention_work(dims, step):
    return _log("attention_work")(dims.dense, step)


def routed_expected(dims, kind):
    return _log("routed_expected")(dims.dense, kind)
