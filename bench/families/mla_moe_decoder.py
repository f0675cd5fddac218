"""The MLA + MoE decoder family (DeepSeek-V2's block), cut to one chip's
share of an expert-parallel deployment.

A configuration file of this family holds the published config's keys,
with `num_hidden_layers` the layers kept and `n_routed_experts` the routed
experts this chip holds of each MoE layer (both under `reduced`, the
published counts under `published`), `expert_parallel` the ways each MoE
layer's experts are split along the router's groups, and `held_group` the
group held here. The router keeps all of the published experts.

The contract is `bench.harness.FAMILY_CONTRACT`; the weights are
`bench.weights_mla_moe`'s, the plain reference `bench.reference.mla_moe`.

Work counts, from the five logged step keys only (M = batch * q_len new
rows, q0 = q_start; every operand bf16, 2 bytes, but the router's weight
float32; the counts are what the algorithm needs, not what the program
executes):

- `step_gemms`: the GEMMs the step routes through `pmm`, each layer:
  `mla.q_down` (M, QL, D), `mla.q_up` (M, H(dn+dr), QL), `mla.kv_down`
  (M, R, D), `mla.k_rope` (M, dr, D), `mla.o` (M, D, H dv); the dense
  layers' `mlp.gate`, `mlp.up` (M, F, D), `mlp.down` (M, D, F); the MoE
  layers' `moe.router` (M, E, D) and shared experts `moe.shared_gate`,
  `moe.shared_up` (M, S f, D), `moe.shared_down` (M, D, S f); and
  `lm_head` (logits_rows, V, D) where logits are used.
- `attention_work`, what runs under `pattn.mla.*`: the latent-space scores
  2 H (R + dr) and weighted sum 2 H R FLOPs per (query, key) pair, causal
  over the real context (q_len q0 + q_len (q_len + 1) / 2 pairs a
  sequence); bytes: the latent cache of the context up to the last new
  position (R + dr values a position) read once, and the queries
  (H (R + dr) a row) and outputs (H R a row) once, every layer.
- `step_work`: FLOPs of the step's GEMMs, the absorbed query and value
  contractions (2 M H dn R each, a layer), the attention above, and the
  held experts under uniform routing: each token picks each of the E
  experts with probability k / E, so the held ones take M k Eh / E rows
  (0.75 a token at the cut) of 2 * 3 D f FLOPs each, a MoE layer. Bytes:
  every weight outside the routed experts read once (the embedding only
  for the rows gathered; the untied head whenever logits are used), the
  held experts a step of M tokens is expected to touch,
  Eh (1 - (1 - k/E)^M) of them, read once, the latent cache of the
  context read once and the new rows written once, and the used logits
  written once in float32.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

from bench import weights_mla_moe as weights
from bench.flops import BF16, F32, gemm_flops
from bench.reference.mla_moe import served_gaps
from repro.configs import get_config

Gemm = Tuple[str, int, int, int]
# A token whose routing, at any MoE layer, lies closer than this (in
# logit units, `bench.reference.mla_moe.held_margin`) to deciding the held
# experts' part of its routed sum is not held to the reference: bf16
# rounding decides such a near-tie. Measured on one v5e at the cut: the
# bf16 program's router logits differ from the reference's by up to 0.054
# (first MoE layer) and 0.155 (last), and every program token with a gap
# over 0.2 lay within 0.076 of a tie (5 seeds); this is twice that.
ROUTING_TIE = 0.15
YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
             "beta_slow", "mscale", "mscale_all_dim")


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    dense_layers: int
    moe_layers: int
    heads: int
    q_lora: int
    kv_lora: int
    nope: int
    rope: int
    v_head: int
    ffn: int
    experts: int            # routed experts the router scores (published)
    held: int               # routed experts held here, each MoE layer
    first_held: int
    top_k: int
    n_group: int
    topk_group: int
    expert_ffn: int
    shared: int
    vocab: int
    routed_scale: float
    norm_topk: bool
    eps: float
    theta: float
    yarn: Tuple[float, ...]     # YARN_KEYS, in that order

    @property
    def layers(self) -> int:
        return self.dense_layers + self.moe_layers

    def attn_params(self) -> int:
        h = self.heads
        return (self.d * self.q_lora + self.q_lora * h * (self.nope + self.rope)
                + self.d * (self.kv_lora + self.rope)
                + self.kv_lora * h * (self.nope + self.v_head)
                + h * self.v_head * self.d)

    def latent_bytes_per_position(self) -> int:
        """The cached latent and rotary key of one position, one layer."""
        return (self.kv_lora + self.rope) * BF16


def dims(config: dict) -> Dims:
    held = config["n_routed_experts"]
    dense = config["first_k_dense_replace"]
    rs = config["rope_scaling"]
    return Dims(
        d=config["hidden_size"], dense_layers=dense,
        moe_layers=config["num_hidden_layers"] - dense,
        heads=config["num_attention_heads"], q_lora=config["q_lora_rank"],
        kv_lora=config["kv_lora_rank"], nope=config["qk_nope_head_dim"],
        rope=config["qk_rope_head_dim"], v_head=config["v_head_dim"],
        ffn=config["intermediate_size"],
        experts=held * config["expert_parallel"], held=held,
        first_held=held * config["held_group"],
        top_k=config["num_experts_per_tok"], n_group=config["n_group"],
        topk_group=config["topk_group"],
        expert_ffn=config["moe_intermediate_size"],
        shared=config["n_shared_experts"], vocab=config["vocab_size"],
        routed_scale=float(config["routed_scaling_factor"]),
        norm_topk=bool(config["norm_topk_prob"]),
        eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
        yarn=tuple(float(rs[k]) for k in YARN_KEYS))


def program_config(config: dict):
    """The registry configuration the file names, checked against every
    published width and routing number the file holds, then cut: the
    file's layers, and the held group's experts. A program whose
    configuration lacks any of these mechanisms is refused."""
    cfg = get_config(config["arch"])
    d = dims(config)
    if config["published"]["n_routed_experts"] != d.experts:
        raise SystemExit(f"{config['arch']}: {d.held} experts held "
                         f"{config['expert_parallel']} ways are not the "
                         f"published {config['published']['n_routed_experts']}")
    yarn = getattr(cfg, "rope_scaling", None)
    got = (cfg.d_model, cfg.n_heads, cfg.attn, cfg.q_lora_rank,
           cfg.kv_lora_rank, cfg.nope_head_dim, cfg.rope_head_dim,
           cfg.nope_head_dim, cfg.d_ff, cfg.n_experts, cfg.moe_top_k,
           cfg.moe_d_ff, cfg.n_shared_experts, cfg.n_dense_layers,
           cfg.vocab, cfg.tie_embeddings, cfg.rope_theta, cfg.norm,
           cfg.act, getattr(cfg, "moe_n_group", None),
           getattr(cfg, "moe_topk_group", None),
           getattr(cfg, "moe_norm_topk", None),
           getattr(cfg, "moe_routed_scale", None),
           None if yarn is None else (
               yarn.factor, yarn.original_max_position, yarn.beta_fast,
               yarn.beta_slow, yarn.mscale, yarn.mscale_all_dim))
    want = (d.d, d.heads, "mla", d.q_lora, d.kv_lora, d.nope, d.rope,
            d.v_head, d.ffn, d.experts, d.top_k, d.expert_ffn, d.shared,
            d.dense_layers, d.vocab, config["tie_word_embeddings"], d.theta,
            "rmsnorm", "swiglu", d.n_group, d.topk_group, d.norm_topk,
            d.routed_scale, d.yarn)
    if got != want or d.eps != 1e-6 or not hasattr(cfg, "moe_n_held"):
        raise SystemExit(f"{config['arch']}: the program's configuration "
                         f"{got} is not the file's {want}")
    return dataclasses.replace(cfg, n_layers=d.layers,
                               moe_first_expert=d.first_held,
                               moe_n_held=d.held)


def make_weights(config: dict, seed: int, sharding=None):
    return weights.make(dims(config), seed, sharding)


def to_program(w: dict, config: dict) -> dict:
    return weights.to_program(w)


def reference_gaps(w: dict, config: dict, tokens, start, served,
                   n_out: int, control: bool):
    """(gap of each served token below the reference's best logit, gap of
    the token the float8 control puts first), each (n_out,); 0 where the
    reference's routing is within `ROUTING_TIE` of a tie."""
    return served_gaps(w, tokens, start, served, dims=dims(config),
                       n_out=n_out, control=control, tie=ROUTING_TIE)


# -- work counts ----------------------------------------------------------

def _attn_gemms(dims: Dims, m: int) -> List[Gemm]:
    h = dims.heads
    return [("mla.q_down", m, dims.q_lora, dims.d),
            ("mla.q_up", m, h * (dims.nope + dims.rope), dims.q_lora),
            ("mla.kv_down", m, dims.kv_lora, dims.d),
            ("mla.k_rope", m, dims.rope, dims.d),
            ("mla.o", m, dims.d, h * dims.v_head)]


def _dense_gemms(dims: Dims, m: int) -> List[Gemm]:
    return _attn_gemms(dims, m) + [
        ("mlp.gate", m, dims.ffn, dims.d), ("mlp.up", m, dims.ffn, dims.d),
        ("mlp.down", m, dims.d, dims.ffn)]


def _moe_gemms(dims: Dims, m: int) -> List[Gemm]:
    sf = dims.shared * dims.expert_ffn
    return _attn_gemms(dims, m) + [
        ("moe.router", m, dims.experts, dims.d),
        ("moe.shared_gate", m, sf, dims.d), ("moe.shared_up", m, sf, dims.d),
        ("moe.shared_down", m, dims.d, sf)]


def step_gemms(dims: Dims, step: dict) -> List[Gemm]:
    m = step["batch"] * step["q_len"]
    out = (_dense_gemms(dims, m) * dims.dense_layers
           + _moe_gemms(dims, m) * dims.moe_layers)
    if step["logits_rows"]:
        out.append(("lm_head", step["logits_rows"], dims.vocab, dims.d))
    return out


def routed_expected(dims: Dims, kind: str) -> Tuple[int, int]:
    """Routed GEMM kernels a step of either kind compiles: one per routed
    projection in each of the two layer loops, and the LM head outside."""
    return len(_dense_gemms(dims, 1)) + len(_moe_gemms(dims, 1)), 1


def _pairs(step: dict) -> int:
    """Causal (query, key) pairs of one sequence."""
    q0, q = step["q_start"], step["q_len"]
    return q * q0 + q * (q + 1) // 2


def attention_work(dims: Dims, step: dict) -> Tuple[float, float]:
    b, q = step["batch"], step["q_len"]
    r, h = dims.kv_lora, dims.heads
    flops = 2.0 * b * _pairs(step) * h * (2 * r + dims.rope) * dims.layers
    cache = b * (step["q_start"] + q) * dims.latent_bytes_per_position()
    rows = b * q * h * (2 * r + dims.rope) * BF16
    return flops, float((cache + rows) * dims.layers)


def _routed_rows(dims: Dims, m: int) -> float:
    """Rows the held experts take under uniform routing, a MoE layer."""
    return m * dims.top_k * dims.held / dims.experts


def _experts_touched(dims: Dims, m: int) -> float:
    return dims.held * (1.0 - (1.0 - dims.top_k / dims.experts) ** m)


def step_work(dims: Dims, step: dict) -> Tuple[float, float]:
    m, rows = step["batch"] * step["q_len"], step["logits_rows"]
    expert = 3 * dims.d * dims.expert_ffn
    flops = sum(gemm_flops(*g[1:]) for g in step_gemms(dims, step))
    flops += (2.0 * gemm_flops(m, dims.kv_lora, dims.nope) * dims.heads
              * dims.layers)
    flops += attention_work(dims, step)[0]
    flops += 2.0 * expert * _routed_rows(dims, m) * dims.moe_layers

    sf = dims.shared * dims.expert_ffn
    dense = dims.attn_params() + 3 * dims.d * dims.ffn
    moe = dims.attn_params() + 3 * dims.d * sf
    nbytes = BF16 * (dense * dims.dense_layers + moe * dims.moe_layers)
    nbytes += F32 * dims.d * dims.experts * dims.moe_layers
    nbytes += BF16 * expert * _experts_touched(dims, m) * dims.moe_layers
    nbytes += BF16 * dims.d * (m + (dims.vocab if rows else 0))
    nbytes += (step["batch"] * (step["q_start"] + step["q_len"])
               * dims.latent_bytes_per_position() * dims.layers)
    nbytes += rows * dims.vocab * F32
    return flops, float(nbytes)
