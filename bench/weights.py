"""Random weights made from the run's seed, and the adapter that hands them
to the program.

The benchmark makes the weights itself, on the device, in one jitted call,
in the dtype they are served in (bf16). The plain reference makes them again
from the same seed with the same function, so it takes nothing the program
made. `to_program` is the one place that knows the program's parameter
layout: a change of that layout is followed here and nowhere else.

Canonical layout (layers stacked on a leading axis):

    embed (V, D); wq (L, D, H*hd); wk, wv (L, D, Hkv*hd); wo (L, H*hd, D);
    gate, up (L, D, F); down (L, F, D);
    rmsnorm only: ln1, ln2 (L, D) and ln_f (D,) in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.flops import Dims

NORM_SCALE_STD = 0.1


def _shapes(dims: Dims, norm: str) -> dict:
    q = dims.heads * dims.head_dim
    kv = dims.kv_heads * dims.head_dim
    L, D = dims.layers, dims.d
    shapes = {"embed": (dims.vocab, D), "wq": (L, D, q), "wk": (L, D, kv),
              "wv": (L, D, kv), "wo": (L, q, D), "gate": (L, D, dims.ffn),
              "up": (L, D, dims.ffn), "down": (L, dims.ffn, D)}
    if norm == "rmsnorm":
        shapes.update(ln1=(L, D), ln2=(L, D), ln_f=(D,))
    return shapes


def _fan_in(name: str, shape) -> int:
    return shape[-1] if name == "embed" else shape[-2]


def make(dims: Dims, norm: str, seed: int, sharding=None) -> dict:
    """The canonical weights of `seed`, made on the device in one call."""
    shapes = _shapes(dims, norm)

    def init(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, i)
            if name.startswith("ln"):
                out[name] = 1.0 + NORM_SCALE_STD * jax.random.normal(
                    k, shape, jnp.float32)
            else:
                scale = _fan_in(name, shape) ** -0.5
                out[name] = (jax.random.normal(k, shape, jnp.float32)
                             * scale).astype(jnp.bfloat16)
        return out

    fn = jax.jit(init, out_shardings=sharding)
    return fn(seed_key(seed))


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, 64-bit seeds included."""
    hi, lo = divmod(int(seed), 2 ** 32)
    key = jax.random.PRNGKey(lo)
    return jax.random.fold_in(key, hi % 2 ** 32) if hi else key


def to_program(w: dict, norm: str) -> dict:
    """The program's parameter pytree (`repro.models.model.init_params`
    layout for a dense decoder with tied embeddings), sharing the arrays."""
    def scale(name):
        return {"scale": w[name]} if norm == "rmsnorm" else {}

    return {
        "embed": w["embed"],
        "ln_f": scale("ln_f"),
        "layers": {
            "ln1": scale("ln1"),
            "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"],
                     "wo": w["wo"]},
            "ln2": scale("ln2"),
            "mlp": {"gate": w["gate"], "up": w["up"], "down": w["down"]},
        },
    }
