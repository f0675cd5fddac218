"""Random weights of an MLA + MoE decoder (DeepSeek-V2's block) made from
the run's seed, and the adapter that hands them to the program.

As `bench.weights` does for the dense decoder: the benchmark makes the
weights itself, on the device, in one jitted call, in the dtype they are
served in (bf16; the router and the norm scales float32, as the program
holds them), and the plain reference makes them again from the same seed
with the same function. `to_program` is the one place that knows the
program's parameter layout.

Canonical layout: `embed` (V, D), `lm_head` (D, V), `ln_f` (D,), and for
each layer group `g` of `GROUPS` (`dense`: the leading dense layers,
`moe`: the MoE layers), stacked on a leading axis of its n layers:

    g.ln1, g.ln2 (n, D);  g.w_dq (n, D, QL);  g.q_norm (n, QL);
    g.w_uq (n, QL, H*(dn+dr));  g.w_dkv (n, D, R);  g.kv_norm (n, R);
    g.w_kr (n, D, dr);  g.w_uk (n, R, H*dn);  g.w_uv (n, R, H*dv);
    g.wo (n, H*dv, D);
    dense only: dense.gate, dense.up (n, D, F); dense.down (n, F, D);
    moe only: moe.router (n, D, E) over all E routed experts;
        moe.gate, moe.up (n, Eh, D, f); moe.down (n, Eh, f, D) for the Eh
        held ones; moe.shared_gate, moe.shared_up (n, D, S*f);
        moe.shared_down (n, S*f, D).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.weights import NORM_SCALE_STD, seed_key

GROUPS = ("dense", "moe")
FLOAT32 = ("router",)           # held in float32, besides the norm scales
NORMS = ("ln1", "ln2", "q_norm", "kv_norm", "ln_f")


def shapes(dims) -> dict:
    """Canonical name -> shape for `dims` (the family's shape object)."""
    D, H = dims.d, dims.heads
    out = {"embed": (dims.vocab, D), "lm_head": (D, dims.vocab),
           "ln_f": (D,)}
    for g, n in (("dense", dims.dense_layers), ("moe", dims.moe_layers)):
        out.update({
            f"{g}.ln1": (n, D), f"{g}.ln2": (n, D),
            f"{g}.w_dq": (n, D, dims.q_lora),
            f"{g}.q_norm": (n, dims.q_lora),
            f"{g}.w_uq": (n, dims.q_lora, H * (dims.nope + dims.rope)),
            f"{g}.w_dkv": (n, D, dims.kv_lora),
            f"{g}.kv_norm": (n, dims.kv_lora),
            f"{g}.w_kr": (n, D, dims.rope),
            f"{g}.w_uk": (n, dims.kv_lora, H * dims.nope),
            f"{g}.w_uv": (n, dims.kv_lora, H * dims.v_head),
            f"{g}.wo": (n, H * dims.v_head, D)})
    n, f = dims.dense_layers, dims.ffn
    out.update({"dense.gate": (n, D, f), "dense.up": (n, D, f),
                "dense.down": (n, f, D)})
    n, f, eh = dims.moe_layers, dims.expert_ffn, dims.held
    sf = dims.shared * dims.expert_ffn
    out.update({"moe.router": (n, D, dims.experts),
                "moe.gate": (n, eh, D, f), "moe.up": (n, eh, D, f),
                "moe.down": (n, eh, f, D),
                "moe.shared_gate": (n, D, sf), "moe.shared_up": (n, D, sf),
                "moe.shared_down": (n, sf, D)})
    return out


def _kind(name: str) -> str:
    return name.rsplit(".", 1)[-1]


def make(dims, seed: int, sharding=None) -> dict:
    """The canonical weights of `seed`, made on the device in one call:
    norm scales 1 + N(0, 0.1^2); the embedding N(0, 1/D); every other
    matrix N(0, 1/fan_in)."""
    table = shapes(dims)

    def init(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(table.items())):
            k = jax.random.fold_in(key, i)
            kind = _kind(name)
            if kind in NORMS:
                out[name] = 1.0 + NORM_SCALE_STD * jax.random.normal(
                    k, shape, jnp.float32)
                continue
            fan_in = shape[-1] if name == "embed" else shape[-2]
            x = jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
            out[name] = x if kind in FLOAT32 else x.astype(jnp.bfloat16)
        return out

    return jax.jit(init, out_shardings=sharding)(seed_key(seed))


def to_program(w: dict) -> dict:
    """The program's parameter pytree (`repro.models.model.init_params`
    layout for an MoE decoder with leading dense layers, MLA with q_lora
    and an untied head), sharing the arrays."""
    def attn(g):
        return {"w_dq": w[f"{g}.w_dq"], "q_norm": {"scale": w[f"{g}.q_norm"]},
                "w_uq": w[f"{g}.w_uq"], "w_dkv": w[f"{g}.w_dkv"],
                "kv_norm": {"scale": w[f"{g}.kv_norm"]},
                "w_kr": w[f"{g}.w_kr"], "w_uk": w[f"{g}.w_uk"],
                "w_uv": w[f"{g}.w_uv"], "wo": w[f"{g}.wo"]}

    def block(g):
        return {"ln1": {"scale": w[f"{g}.ln1"]}, "attn": attn(g),
                "ln2": {"scale": w[f"{g}.ln2"]}}

    return {
        "embed": w["embed"], "lm_head": w["lm_head"],
        "ln_f": {"scale": w["ln_f"]},
        "dense_layers": dict(block("dense"), mlp={
            "gate": w["dense.gate"], "up": w["dense.up"],
            "down": w["dense.down"]}),
        "moe_layers": dict(block("moe"), moe={
            "router": w["moe.router"],
            "experts": {"gate": w["moe.gate"], "up": w["moe.up"],
                        "down": w["moe.down"]},
            "shared": {"gate": w["moe.shared_gate"],
                       "up": w["moe.shared_up"],
                       "down": w["moe.shared_down"]}}),
    }
