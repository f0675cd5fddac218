"""The dense decoder family: a pre-norm decoder with grouped-query (or
multi-head) attention, RoPE on the whole head, a SwiGLU MLP and a tied LM
head, at the published widths of its configuration file.

What the harness and the metrics know of the model, they take from here
(the contract is `bench.harness.FAMILY_CONTRACT`): the program's
configuration, the shape object, the weights and the program's layout of
them, the plain reference, and the work a logged serve step needs. The
code behind it is `bench.flops` (work counts), `bench.weights` (weights
from the seed) and `bench.reference.decoder` (the float32 reference).
"""
from __future__ import annotations

from typing import List, Tuple

from bench import flops, weights
from bench.flops import attention_work, step_work  # noqa: F401
from bench.reference.decoder import served_gaps
from repro.configs import get_config

Gemm = Tuple[str, int, int, int]


def dims(config: dict) -> flops.Dims:
    return flops.Dims.from_config(config)


def program_config(config: dict):
    """The registry configuration the file names, checked against the
    published widths the file holds. The family's files cut nothing, so
    a registry configuration that differs in any of them, or that is not a
    dense SwiGLU decoder, is refused."""
    cfg = get_config(config["arch"])
    d = dims(config)
    got = (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
           cfg.d_ff, cfg.vocab, cfg.norm, cfg.tie_embeddings, cfg.rope_theta)
    want = (d.d, d.layers, d.heads, d.kv_heads, d.head_dim, d.ffn, d.vocab,
            config["norm"], config["tie_word_embeddings"],
            config["rope_theta"])
    if got != want or cfg.n_experts or cfg.attn != "gqa" \
            or cfg.block_pattern != "attn" or cfg.act != "swiglu":
        raise SystemExit(f"{config['arch']}: the program's configuration "
                         f"{got} is not the file's {want}")
    return cfg


def make_weights(config: dict, seed: int, sharding=None):
    return weights.make(dims(config), config["norm"], seed, sharding)


def to_program(w: dict, config: dict) -> dict:
    return weights.to_program(w, config["norm"])


def reference_gaps(w: dict, config: dict, tokens, start, served,
                   n_out: int, control: bool):
    """(gap of each served token below the reference's best logit, gap of
    the token the float8 control puts first), each (n_out,)."""
    return served_gaps(w, tokens, start, served, dims=dims(config),
                       norm=config["norm"], eps=config["norm_eps"],
                       theta=config["rope_theta"], n_out=n_out,
                       control=control)


def step_gemms(dims: flops.Dims, step: dict) -> List[Gemm]:
    return flops.step_gemms(dims, step["batch"], step["q_len"],
                            step["logits_rows"])


def routed_expected(dims: flops.Dims, kind: str) -> Tuple[int, int]:
    """Routed GEMM kernels a step of either kind compiles: one per
    projection inside the layer loop, and the LM head outside it."""
    return len(flops.layer_gemms(dims, 1)), 1
