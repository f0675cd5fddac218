"""ShapeDtypeStruct stand-ins for every (arch x shape) dry-run cell — weak-
type-correct, shardable, zero allocation.

`train` cells lower train_step; `prefill` cells lower the prefill forward;
`decode` cells lower serve_step (ONE new token against a KV cache of
seq_len), per the brief. VLM cells add stub patch embeddings; enc-dec cells
add stub frame embeddings.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.registry import SHAPES
from repro.models.common import ModelConfig
from repro.models.model import decode_init, init_params
from repro.models.workload import frontend_inputs


def sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def param_shapes(cfg: ModelConfig) -> Any:
    return jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))


def cache_shapes(cfg: ModelConfig, batch: int, max_len: int) -> Any:
    return jax.eval_shape(
        lambda: decode_init(param_shapes(cfg), cfg, batch, max_len))


def input_specs(cfg: ModelConfig, shape_name: str) -> Dict[str, Any]:
    """Returns {kind, batch/seq metadata, and the abstract inputs}."""
    spec = SHAPES[shape_name]
    b, s = spec["global_batch"], spec["seq_len"]
    kind = spec["kind"]
    out: Dict[str, Any] = {"kind": kind, "batch": b, "seq": s}

    if kind == "train":
        out["inputs"] = {"tokens": sds((b, s), jnp.int32),
                         "targets": sds((b, s), jnp.int32),
                         **frontend_inputs(cfg, b, kind)}
    elif kind == "prefill":
        out["tokens"] = sds((b, s), jnp.int32)
        out.update(frontend_inputs(cfg, b, kind))
    elif kind == "decode":
        out["tokens"] = sds((b, 1), jnp.int32)
        out["position"] = sds((), jnp.int32)
        out["caches"] = cache_shapes(cfg, b, s)
        out.update(frontend_inputs(cfg, b, kind))
    else:
        raise ValueError(kind)
    return out
