"""Mixture-of-Experts layer (DeepSeek-V2 / DeepSeekMoE style: fine-grained
routed experts + always-on shared experts, top-k routing).

Routing (`route`) follows DeepSeek-V2's gate: float32 logits over all
`n_experts`, a softmax, then either plain top-k or `group_limited_greedy`
(each group scored by its best expert, the top `moe_topk_group` groups
kept, top-k among their experts); the k weights are renormalised or scaled
by `moe_routed_scale`.

The layer holds the routed experts `cfg.held_experts` names (all by
default): one chip's share of an expert-parallel deployment. It computes
their part of the routed sum for every token routed to them, dropping
none: the T*k (token, choice) rows are sorted by expert, rows of experts
held elsewhere last, and one grouped GEMM per projection (`_grouped`: the
Pallas `megablox.gmm` on the TPU, `jax.lax.ragged_dot` elsewhere) runs
each held expert over its own rows. Shapes stay static: the row budget is
T*k, what the held experts take when every choice lands on them; on the
TPU the kernel's grid stops at the last held expert's rows. The shared
experts run on every token through `pmm`.

Under an activation mesh (the production-mesh dry run) the experts are
sharded over the 'model' axis and the layer keeps the capacity-bounded
one-hot einsum dispatch (Switch-style), a dense-algebra SPMD program whose
dispatch/combine lower to the collectives XLA picks; tokens past
`capacity_factor` are dropped there.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from jax.experimental.pallas.ops.tpu.megablox import gmm as megablox_gmm

from repro.models import shard_ctx
from repro.models.common import ModelConfig, Params, activation, dense_init, mlp_params
from repro.models.matmul import StoredWeight, pmm, record_gemm

# (rows, contraction, columns) of a grouped-GEMM kernel step on the TPU:
# the fastest of six tried on v5e at DeepSeek-V2's expert shapes
GMM_TILING = (128, 512, 1536)


def moe_params(key, cfg: ModelConfig) -> Params:
    k_router, k_shared, k_experts = jax.random.split(key, 3)
    e, d, f = cfg.held_experts[1], cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(k_experts, 3)
    scale = d ** -0.5
    p = {
        "router": dense_init(k_router, d, cfg.n_experts, jnp.float32),
        "experts": {
            "gate": (jax.random.normal(ks[0], (e, d, f), jnp.float32) * scale).astype(cfg.dtype),
            "up": (jax.random.normal(ks[1], (e, d, f), jnp.float32) * scale).astype(cfg.dtype),
            "down": (jax.random.normal(ks[2], (e, f, d), jnp.float32) * (f ** -0.5)).astype(cfg.dtype),
        },
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_params(k_shared, cfg,
                                 d_ff=cfg.moe_d_ff * cfg.n_shared_experts)
    return p


def route(logits: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """Top-k routing weights (..., k) float32 and expert ids (..., k) from
    float32 router logits (..., n_experts)."""
    scores = jax.nn.softmax(logits, axis=-1)
    groups = cfg.moe_n_group
    if groups > 1:
        per = cfg.n_experts // groups
        best = scores.reshape(*scores.shape[:-1], groups, per).max(-1)
        _, keep = jax.lax.top_k(best, cfg.moe_topk_group)
        kept = jax.nn.one_hot(keep, groups, dtype=jnp.bool_).any(-2)
        scores = jnp.where(jnp.repeat(kept, per, axis=-1), scores, 0.0)
    topv, topi = jax.lax.top_k(scores, cfg.moe_top_k)
    if cfg.moe_norm_topk:
        topv = topv / jnp.clip(topv.sum(-1, keepdims=True), 1e-9)
    else:
        topv = topv * cfg.moe_routed_scale
    return topv, topi


def _grouped(x: jax.Array, w, sizes: jax.Array) -> jax.Array:
    """x (M, K) with rows sorted by group, w (G, K, N), sizes (G,) -> (M, N):
    each group's rows times its weight. On the TPU, the Pallas grouped
    matmul (`megablox.gmm`), whose grid visits only the row tiles that hold
    a group's rows: the rows past the last group are neither computed nor
    written. Elsewhere `jax.lax.ragged_dot`, which gives them zeros.

    `w` may be one layer of a stacked (L, G, K, N) weight (`StoredWeight`):
    the GEMM then reads it where it lies, from the (L·G, K, N) view of the
    stack (a bitcast), with the sizes at the layer's groups and zero at
    every other layer's, whose experts the kernel never visits."""
    if isinstance(w, StoredWeight):
        n_layers, g = w.stored.shape[:2]
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n_layers * g,), sizes.dtype), sizes, (w.layer * g,))
        w = w.stored.reshape(n_layers * g, *w.shape[1:])
    if jax.default_backend() != "tpu":
        return jax.lax.ragged_dot(x, w, sizes)
    tiling = tuple(min(t, n) for t, n in zip(GMM_TILING,
                                             (x.shape[0], *w.shape[1:])))
    return megablox_gmm(x, w, sizes, preferred_element_type=x.dtype,
                        tiling=tiling)


def _held_experts(p: Params, xt: jax.Array, topv: jax.Array,
                  topi: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The held experts' part of the routed sum, (T, D), with no token
    dropped: T*k rows sorted by expert, one grouped GEMM per projection."""
    t, d = xt.shape
    k = topi.shape[-1]
    first, n = cfg.held_experts
    f = p["experts"]["gate"].shape[-1]
    with jax.named_scope("moe.dispatch"):
        local = topi.reshape(t * k) - first
        held = (local >= 0) & (local < n)
        group = jnp.where(held, local, n)       # rows held elsewhere: last
        order = jnp.argsort(group, stable=True)
        sizes = jnp.zeros((n + 1,), jnp.int32).at[group].add(1)[:n]
        xs = jnp.take(xt, order // k, axis=0)                       # (T*k, D)
    with jax.named_scope("moe.experts"):
        # static row budget: all T*k rows; the GEMMs run each held
        # expert over its own rows (`sizes`), the rest are left out
        record_gemm("moe.expert_gate", t * k, f, d)
        record_gemm("moe.expert_up", t * k, f, d)
        record_gemm("moe.expert_down", t * k, d, f)
        ex = p["experts"]
        h = activation(cfg, _grouped(xs, ex["gate"], sizes),
                       _grouped(xs, ex["up"], sizes))
        ys = _grouped(h, ex["down"], sizes)                         # (T*k, D)
    with jax.named_scope("moe.combine"):
        w = topv.reshape(t * k)[order]
        # rows past the held experts' are not written on the TPU (any
        # value, NaN included): select, never scale
        ys = jnp.where(held[order][:, None],
                       ys.astype(jnp.float32) * w[:, None], 0.0)
        back = jnp.argsort(order)
        out = jnp.take(ys, back, axis=0).reshape(t, k, d).sum(1)
    return out.astype(xt.dtype)


# -- expert-sharded mesh path ------------------------------------------------

def _capacity(tokens: int, cfg: ModelConfig) -> int:
    cap = int(tokens * cfg.moe_top_k * cfg.capacity_factor / cfg.n_experts)
    return max(cap, 4)


_GROUP_TOKENS = 512   # dispatch-group size; the einsum dispatch costs
                      # O(E * cap) = O(k * cf * GROUP_TOKENS) MACs per token,
                      # so small fixed groups keep routing overhead ~10-15% of
                      # the expert GEMMs regardless of global batch.


def _dp_groups(t: int, mesh) -> int:
    """Dispatch groups: fixed-size token groups (per-group capacity — the
    standard EP formulation computes routing positions within a local shard).
    The group count is kept a multiple of the DP shard count so the group dim
    shards cleanly over dp; the dispatch tensor is (G, TL, E, cap) — sharded
    (dp, -, EP, -) it stays small per device instead of the global-capacity
    O(T^2 k / E) blow-up."""
    dp = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            dp *= mesh.shape[a]
    if t % dp:
        dp = 1
    g = dp
    while t % (g * 2) == 0 and t // (g * 2) >= _GROUP_TOKENS:
        g *= 2
    return g


def _constrain(x: jax.Array, mesh, *spec) -> jax.Array:
    from jax.sharding import NamedSharding, PartitionSpec as P
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    resolved = [dp if s == "dp" else s for s in spec]
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*resolved)))


def _capacity_dispatch(p: Params, xt: jax.Array, topv: jax.Array,
                       topi: jax.Array, cfg: ModelConfig, mesh) -> jax.Array:
    """The routed sum over all experts, sharded over 'model', through
    capacity-bounded one-hot einsums; (T, D)."""
    if cfg.held_experts != (0, cfg.n_experts):
        raise ValueError("the expert-sharded mesh path holds every expert")
    t, d = xt.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    g = _dp_groups(t, mesh)
    tl = t // g
    cap = _capacity(tl, cfg)
    xt = _constrain(xt.reshape(g, tl, d), mesh, "dp", None, None)
    topv, topi = topv.reshape(g, tl, k), topi.reshape(g, tl, k)

    # position of each (token, choice) inside its expert's per-group buffer
    onehot = jax.nn.one_hot(topi, e, dtype=jnp.int32)                 # (G,TL,k,E)
    flat = onehot.reshape(g, tl * k, e)
    pos = jnp.cumsum(flat, axis=1) * flat - 1
    pos = pos.reshape(g, tl, k, e)
    pos_tk = (pos * onehot).sum(-1)                                   # (G,TL,k)
    within = (pos_tk >= 0) & (pos_tk < cap)
    pos_c = jnp.clip(pos_tk, 0, cap - 1)

    # one-hot einsum dispatch. Scatter/gather dispatch (moves exactly T*k
    # D-vectors, no (G,TL,E,cap) tensor) was REFUTED on the 512-device
    # mesh: XLA cannot partition the scatter over the expert axis and
    # replicates the updates (deepseek-v2 train peak went 152 -> 639
    # GB/dev, collective term 6 -> 347 s). The einsum form stays
    # SPMD-friendly because every op is dense contraction.
    within_f = within[..., None].astype(xt.dtype)
    oh_cap = jax.nn.one_hot(pos_c, cap, dtype=xt.dtype)               # (G,TL,k,cap)
    sel = jax.nn.one_hot(topi, e, dtype=xt.dtype) * within_f          # (G,TL,k,E)
    disp = jnp.einsum("gtke,gtkc->gtec", sel, oh_cap)                 # (G,TL,E,cap)
    disp = _constrain(disp, mesh, "dp", None, "model", None)
    comb = jnp.einsum("gtke,gtkc->gtec",
                      sel.astype(jnp.float32) * topv[..., None],
                      oh_cap.astype(jnp.float32))
    comb = _constrain(comb, mesh, "dp", None, "model", None)

    # expert GEMMs batched over (G, E), sharded (dp, EP); each logical
    # (cap, d) x (d, f) problem is logged for the observed workload.
    record_gemm("moe.expert_gate", cap, p["experts"]["gate"].shape[-1], d)
    record_gemm("moe.expert_down", cap, d, p["experts"]["down"].shape[-2])
    xe = jnp.einsum("gtec,gtd->gecd", disp, xt)                       # (G,E,cap,D)
    xe = _constrain(xe, mesh, "dp", "model", None, None)
    h = activation(cfg,
                   jnp.einsum("gecd,edf->gecf", xe, p["experts"]["gate"]),
                   jnp.einsum("gecd,edf->gecf", xe, p["experts"]["up"]))
    h = _constrain(h, mesh, "dp", "model", None, None)
    ye = jnp.einsum("gecf,efd->gecd", h, p["experts"]["down"])        # (G,E,cap,D)
    ye = _constrain(ye, mesh, "dp", "model", None, None)
    out = jnp.einsum("gtec,gecd->gtd", comb.astype(xt.dtype), ye)
    return _constrain(out, mesh, "dp", None, None).reshape(t, d)


def apply_moe(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """x: (B, S, D) -> (B, S, D): the held experts' part of the routed sum
    plus the shared experts."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    with jax.named_scope("moe.router"):
        logits = pmm(xt.astype(jnp.float32), p["router"], tag="moe.router")
        topv, topi = route(logits, cfg)
    mesh = shard_ctx.get_mesh()
    if mesh is None:
        out = _held_experts(p, xt, topv, topi, cfg)
    else:
        out = _capacity_dispatch(p, xt, topv, topi, cfg, mesh)
    if cfg.n_shared_experts:
        sh = activation(cfg, pmm(xt, p["shared"]["gate"], tag="moe.shared_gate"),
                        pmm(xt, p["shared"]["up"], tag="moe.shared_up"))
        out = out + pmm(sh, p["shared"]["down"], tag="moe.shared_down")
    return out.reshape(b, s, d)
