"""Model configuration + shared layer primitives (pure JAX, no flax).

Parameters are plain nested dicts (pytrees). Every assigned architecture is
expressible as a `ModelConfig`; block kinds cover dense attention, MLA, MoE,
Mamba2, sLSTM/mLSTM and encoder-decoder stacks. Layers are written against
`jnp` ops only so the whole stack lowers under pjit on any mesh.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.matmul import pmm

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN RoPE scaling (DeepSeek-V2's `rope_scaling` of type "yarn"):
    the rotary frequencies are stretched by `factor` below a ramp between
    the correction dims of `beta_fast` and `beta_slow` rotations over the
    `original_max_position` positions, and the softmax scale grows by the
    square of `mscale_all_dim`'s attention factor."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    @staticmethod
    def _mscale(factor: float, mscale: float) -> float:
        return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0

    def correction_range(self, dim: int, theta: float) -> Tuple[int, int]:
        """The rotary pair indices where the ramp starts and ends (HF's
        `yarn_find_correction_range`)."""
        def at(rotations):
            return (dim * math.log(self.original_max_position
                                   / (rotations * 2 * math.pi))
                    / (2 * math.log(theta)))
        return (max(math.floor(at(self.beta_fast)), 0),
                min(math.ceil(at(self.beta_slow)), dim - 1))

    def inv_freq(self, dim: int, theta: float) -> np.ndarray:
        """(dim/2,) rotary frequencies: the original ones below the ramp,
        divided by `factor` above it, blended linearly across it."""
        base = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
        lo, hi = self.correction_range(dim, theta)
        ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
        return (base / self.factor * ramp + base * (1 - ramp)).astype(
            np.float32)

    def rope_factor(self) -> float:
        """The factor on the cos/sin tables (1 where mscale equals
        mscale_all_dim)."""
        return (self._mscale(self.factor, self.mscale)
                / self._mscale(self.factor, self.mscale_all_dim))

    def softmax_factor(self) -> float:
        """The factor on the softmax scale, (0.1 m ln s + 1)^2."""
        if not self.mscale_all_dim:
            return 1.0
        return self._mscale(self.factor, self.mscale_all_dim) ** 2


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None          # default d_model // n_heads
    act: str = "swiglu"                     # swiglu | geglu | gelu
    norm: str = "rmsnorm"                   # rmsnorm | layernorm | nonparam_ln
    qk_norm: bool = False
    rope_theta: float = 10000.0
    rope_scaling: Optional[YaRN] = None     # DeepSeek-V2's YaRN (MLA rotary part)
    tie_embeddings: bool = True
    # attention family
    attn: str = "gqa"                       # gqa | mla
    # MLA (DeepSeek-V2)
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    rope_head_dim: int = 64
    nope_head_dim: int = 128
    # MoE
    n_experts: int = 0
    n_shared_experts: int = 0
    moe_top_k: int = 0
    moe_d_ff: int = 0
    n_dense_layers: int = 0                 # leading dense layers before MoE
    moe_n_group: int = 1                    # routing groups (group_limited_greedy)
    moe_topk_group: int = 1                 # groups each token may pick from
    moe_norm_topk: bool = True              # renormalise the top-k weights ...
    moe_routed_scale: float = 1.0           # ... else scale them by this
    moe_first_expert: int = 0               # the experts this layer holds:
    moe_n_held: int = 0                     # first index and count (0 = all)
    capacity_factor: float = 1.25           # expert-sharded mesh path only
    # SSM / hybrid / xlstm
    block_pattern: str = "attn"             # attn | mamba2_hybrid | xlstm
    ssm_state: int = 0
    mamba_headdim: int = 64
    hybrid_attn_every: int = 6              # shared attn block period (zamba2)
    slstm_every: int = 8                    # sLSTM period in xlstm
    # encoder-decoder
    is_encoder_decoder: bool = False
    n_encoder_layers: int = 0
    # modality frontend stub: prefix embeddings prepended to the sequence
    frontend: str = "none"                  # none | vision_stub | audio_stub
    n_prefix: int = 0                       # patches / frames per example
    dtype: Any = jnp.bfloat16

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def held_experts(self) -> Tuple[int, int]:
        """(first expert index, count) of the routed experts held here."""
        return self.moe_first_expert, self.moe_n_held or self.n_experts

    def param_count(self) -> int:
        """Total parameters (for 6ND model-FLOPs accounting)."""
        leaves = jax.tree.leaves(jax.eval_shape(lambda: init_placeholder(self)))
        return sum(int(math.prod(l.shape)) for l in leaves)

    def active_param_count(self) -> int:
        """Parameters touched per token (MoE: shared + top-k experts only)."""
        total = self.param_count()
        if not self.n_experts:
            return total
        expert = 3 * self.d_model * self.moe_d_ff  # gate+up+down per expert
        n_moe_layers = self.n_layers - self.n_dense_layers
        held = self.held_experts[1]
        inactive = (held - min(self.moe_top_k, held)) * expert * n_moe_layers
        return total - inactive


def init_placeholder(cfg: "ModelConfig"):
    # set lazily by model.py to avoid a circular import
    from repro.models.model import init_params
    return init_params(jax.random.PRNGKey(0), cfg)


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), dtype=jnp.float32)
            * scale).astype(dtype)


def norm_params(cfg: ModelConfig) -> Params:
    if cfg.norm == "nonparam_ln":
        return {}
    return {"scale": jnp.ones((cfg.d_model,), dtype=jnp.float32)}


def apply_norm(p: Params, x: jax.Array, cfg: ModelConfig, eps: float = 1e-6) -> jax.Array:
    with jax.named_scope("norm"):
        if cfg.norm == "rmsnorm":
            return rms_norm(p, x, eps)
        xf = x.astype(jnp.float32)
        if cfg.norm == "layernorm":
            mu = jnp.mean(xf, axis=-1, keepdims=True)
            var = jnp.var(xf, axis=-1, keepdims=True)
            xf = (xf - mu) * jax.lax.rsqrt(var + eps) * p["scale"]
        elif cfg.norm == "nonparam_ln":  # OLMo: no learned scale/bias
            mu = jnp.mean(xf, axis=-1, keepdims=True)
            var = jnp.var(xf, axis=-1, keepdims=True)
            xf = (xf - mu) * jax.lax.rsqrt(var + eps)
        else:
            raise ValueError(cfg.norm)
        return xf.astype(x.dtype)


def rms_norm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm with a learned scale over the last axis, in float32 (the
    caller names its scope)."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * p["scale"]).astype(x.dtype)


def rms_head_norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    """qk-norm: per-head RMS normalization (Qwen3)."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return xf.astype(x.dtype)


def rope_tables(positions: jax.Array, dim: int, theta: float,
                scaling: Optional[YaRN] = None) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables for given integer positions: (..., dim/2), with
    YaRN's frequencies and table factor where `scaling` is given."""
    if scaling is None:
        freqs = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
        factor = 1.0
    else:
        freqs = jnp.asarray(scaling.inv_freq(dim, theta))
        factor = scaling.rope_factor()
    angles = positions.astype(jnp.float32)[..., None] * freqs
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    return cos, sin


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., seq, heads, dim); cos/sin: (..., seq, dim/2)."""
    with jax.named_scope("rope"):
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        c = cos[..., None, :]
        s = sin[..., None, :]
        return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c],
                               axis=-1).astype(x.dtype)


def activation(cfg: ModelConfig, gate: jax.Array, up: jax.Array) -> jax.Array:
    if cfg.act == "swiglu":
        return jax.nn.silu(gate) * up
    if cfg.act == "geglu":
        return jax.nn.gelu(gate, approximate=True) * up
    if cfg.act == "gelu":
        return jax.nn.gelu(gate + up, approximate=True)
    raise ValueError(cfg.act)


def mlp_params(key, cfg: ModelConfig, d_ff: Optional[int] = None) -> Params:
    d_ff = d_ff or cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": dense_init(k1, cfg.d_model, d_ff, cfg.dtype),
        "up": dense_init(k2, cfg.d_model, d_ff, cfg.dtype),
        "down": dense_init(k3, d_ff, cfg.d_model, cfg.dtype),
    }


def apply_mlp(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    h = activation(cfg, pmm(x, p["gate"], tag="mlp.gate"),
                   pmm(x, p["up"], tag="mlp.up"))
    return pmm(h, p["down"], tag="mlp.down")
