"""The GEMMs one model step runs, read from the model's own trace: the
workload launchers warm their planner with, so that the model is the one
place that knows which GEMMs a step runs."""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from repro.core.schedule import GEMMShape
from repro.models import shard_ctx
from repro.models.model import decode_init, decode_step, forward, init_params
from repro.obs import trace as obs_trace


def frontend_inputs(cfg, batch: int, kind: str) -> dict:
    """What a modality-frontend config's step takes besides tokens, as
    abstract values: the VLM stub's patch embeddings and the enc-dec stub's
    frame embeddings for a forward pass ("train", "prefill"), the enc-dec
    encoder output for a decode step."""
    embeds = jax.ShapeDtypeStruct((batch, cfg.n_prefix, cfg.d_model),
                                  cfg.dtype)
    out = {}
    if cfg.frontend == "vision_stub" and kind != "decode":
        out["prefix_embeds"] = embeds
    if cfg.is_encoder_decoder:
        out["encoder_out" if kind == "decode" else "encoder_embeds"] = embeds
    return out


def gemm_workload(cfg, batch: int, seq: int, kind: str = "prefill",
                  mesh=None) -> List[GEMMShape]:
    """Deduplicated GEMM shapes, in trace order, that one step of `cfg`
    logs through `pmm` and `record_gemm` under a record-only `GemmContext`.

    "prefill" and "train" trace `forward` over (batch, seq) tokens,
    "decode" traces `decode_step` of (batch, 1) tokens against a cache of
    `seq` positions. `mesh` is the activation mesh (`shard_ctx.set_mesh`)
    the served program runs under; the MoE layer picks its expert dispatch
    from it. No tracer sees the record-only dispatches.

    Each call traces fresh closures under `jax.eval_shape`, never a
    `jax.jit` object: a trace cached under this record-only context would
    hand a later routed trace an unrouted program.
    """
    extra = frontend_inputs(cfg, batch, kind)
    tokens = jax.ShapeDtypeStruct((batch, 1 if kind == "decode" else seq),
                                  jnp.int32)
    ctx = shard_ctx.GemmContext(mesh=None)
    prev_mesh, prev_tracer = shard_ctx.get_mesh(), obs_trace.get_tracer()
    shard_ctx.set_mesh(mesh)
    obs_trace.set_tracer(None)
    try:
        with shard_ctx.gemm_context(ctx):
            params = jax.eval_shape(
                lambda: init_params(jax.random.PRNGKey(0), cfg))
            if kind == "decode":
                caches = jax.eval_shape(
                    lambda: decode_init({}, cfg, batch, seq))
                jax.eval_shape(
                    lambda p, c, t, i, kw: decode_step(p, c, t, i, cfg, **kw),
                    params, caches, tokens,
                    jax.ShapeDtypeStruct((), jnp.int32), extra)
            else:
                jax.eval_shape(lambda p, t, kw: forward(p, t, cfg, **kw),
                               params, tokens, extra)
    finally:
        shard_ctx.set_mesh(prev_mesh)
        obs_trace.set_tracer(prev_tracer)
    return ctx.stats.observed_shapes()
