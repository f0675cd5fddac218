"""Sharding rule table: parameter/batch/cache PartitionSpecs for the
production mesh — the data-layout half of the DiT schedule at pod scale.

The split-scheme analogy (DESIGN.md §2.2): choosing which mesh axes a tensor's
dims map to IS the paper's §3.2.1 split scheme (which chip's HBM owns which
block); XLA's within-shard layout is the placement scheme.

Policy: 2-D FSDP x TP. Weight matrices shard their input dim over 'data'
(FSDP — gathered on use) and output dim over 'model' (TP). MoE experts shard
the expert dim over 'model' (EP) and d_model over 'data'. Every rule is
fitted: an axis that does not divide the dim is dropped (robustness across
all 10 archs and both meshes). The 'pod' axis is pure DP (it never appears in
weight specs; gradients cross pods in one hierarchical all-reduce).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.common import ModelConfig


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _fit(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop spec axes that don't divide the corresponding dim."""
    out = []
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for dim, entry in zip(shape, entries):
        if entry is None:
            out.append(None)
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        size = 1
        for n in names:
            size *= mesh.shape[n]
        out.append(entry if dim % size == 0 else None)
    return P(*out)


# rules keyed by the LAST path component (parameter name); specs refer to the
# trailing dims of the leaf (leading stacked-layer dims get None).
_W_IN = P("data", "model")     # (d_in, d_out) column-parallel
_W_OUT = P("model", "data")    # (d_in, d_out) row-parallel

PARAM_RULES: Dict[str, P] = {
    "embed": P("model", "data"),          # vocab-parallel embedding
    "lm_head": P("data", "model"),
    "frontend_proj": _W_IN,
    # attention
    "wq": _W_IN, "wk": _W_IN, "wv": _W_IN, "wo": _W_OUT,
    "w_dq": _W_IN, "w_uq": _W_IN, "w_dkv": _W_IN, "w_kr": _W_IN,
    "w_uk": _W_IN, "w_uv": _W_IN,
    # mlp
    "gate": _W_IN, "up": _W_IN, "down": _W_OUT,
    # moe
    "router": P("data", None),
    # ssm / xlstm
    "w_in": _W_IN, "w_out": _W_OUT, "conv": P(None, "model"),
    "a_log": P(None), "d_skip": P(None), "dt_bias": P(None),
    "w_up": _W_IN, "w_q": _W_IN, "w_k": _W_IN, "w_v": _W_IN,
    "w_gates": P("data", None), "w_down": _W_OUT,
    "r": P(None),
    # norms
    "scale": P(None),
}

# MoE expert tensors are 3-D (E, d_in, d_out): EP over 'model' + FSDP 'data'.
MOE_EXPERT_RULES: Dict[str, P] = {
    "gate": P("model", "data", None),
    "up": P("model", "data", None),
    "down": P("model", None, "data"),
}


def param_spec(path: Tuple[Any, ...], leaf: jax.ShapeDtypeStruct,
               mesh: Mesh) -> P:
    keys = [getattr(k, "key", getattr(k, "name", None)) for k in path]
    name = keys[-1]
    in_experts = "experts" in keys
    rule = (MOE_EXPERT_RULES if in_experts else PARAM_RULES).get(name)
    if rule is None:
        rule = P()
    # pad for stacked-layer leading dims
    extra = len(leaf.shape) - len(rule)
    if extra > 0:
        rule = P(*([None] * extra + list(rule)))
    return _fit(rule, leaf.shape, mesh)


def param_shardings(params_shape: Any, mesh: Mesh) -> Any:
    """Pytree of NamedShardings matching a params (or opt-state) shape tree."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, param_spec(path, leaf, mesh)),
        params_shape)


# ---------------------------------------------------------------------------
# batch / cache specs
# ---------------------------------------------------------------------------

def batch_spec(mesh: Mesh) -> P:
    """Token batches: batch over all DP axes."""
    return P(dp_axes(mesh), None)


def cache_spec(path: Tuple[Any, ...], leaf: jax.ShapeDtypeStruct,
               mesh: Mesh, cfg: ModelConfig, batch: int) -> P:
    """Decode caches: batch over DP when it divides; otherwise shard the
    sequence (long_500k batch=1) or the head/state dims over 'model'."""
    keys = [getattr(k, "key", getattr(k, "name", None)) for k in path]
    name = keys[-1]
    dp = dp_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    batch_ok = batch % dp_size == 0

    def bdim(*rest):
        return P(dp if batch_ok else None, *rest)

    if name == "index":
        return P(*([None] * len(leaf.shape)))
    # leading dim is stacked layers, second is batch
    if name in ("k", "v"):          # (L, B, n_kv, S, hd)
        # prefer kv-head sharding over 'model'; GQA archs with n_kv < |model|
        # (qwen3/phi4 kv=8, gemma kv=1) fall back to SEQUENCE sharding — the
        # cache is by far the largest decode tensor (32k x batch) and leaving
        # it replicated over 'model' costs ~16x HBM (observed 172 GB/dev).
        n_kv = leaf.shape[2]
        if n_kv % mesh.shape["model"] == 0:
            spec = P(None, dp if batch_ok else None, "model", None, None)
        else:
            spec = P(None, dp if batch_ok else None, None, "model", None)
        return _fit(spec, leaf.shape, mesh)
    if name == "c_kv":              # (L, B, S, r)
        return _fit(P(None, dp if batch_ok else None,
                      None if batch_ok else "data", "model"), leaf.shape, mesh)
    if name == "k_rope_t":          # (L, B, dr, S)
        return _fit(P(None, dp if batch_ok else None, None,
                      None if batch_ok else "data"), leaf.shape, mesh)
    if name == "h":                 # mamba (L, B, H, N, P) / slstm (L,B,H,hd)
        return _fit(P(None, dp if batch_ok else None, "model"), leaf.shape, mesh)
    if name in ("c", "n", "m"):     # xlstm states (L, B, H, ...)
        return _fit(P(None, dp if batch_ok else None, "model"), leaf.shape, mesh)
    if name == "conv":              # (L, B, 3, C)
        return _fit(P(None, dp if batch_ok else None, None, "model"),
                    leaf.shape, mesh)
    return P(*([None] * len(leaf.shape)))


def cache_shardings(cache_shape: Any, mesh: Mesh, cfg: ModelConfig,
                    batch: int) -> Any:
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, cache_spec(path, leaf, mesh, cfg, batch)),
        cache_shape)
