"""Training and serving step functions — the graphs the dry-run lowers.

`make_train_step` builds a donated, optionally-microbatched step:
loss -> grads (optionally int8-compressed with error feedback before the
cross-pod reduction) -> AdamW update. Remat is on by default (scan-level
jax.checkpoint). `make_serve_step` wraps decode_step for batched requests.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig
from repro.models.matmul import weight_value
from repro.models.model import decode_step, forward, lm_head_weight
from repro.optim import adamw, compress

LOSS_CHUNK = 512


def _constrain_logits(x, vocab):
    from repro.models import shard_ctx
    mesh = shard_ctx.get_mesh()
    if mesh is None or vocab % mesh.shape["model"]:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(dp, None, "model")))


def chunked_xent(hidden: jax.Array, head_w: jax.Array, targets: jax.Array,
                 vocab: int, chunk: int = LOSS_CHUNK) -> jax.Array:
    """Fused softmax-CE: project vocab logits chunk-by-chunk along the
    sequence (remat'd scan) so the fp32 (B, S, V) tensor never exists —
    unsharded-vocab archs were paying up to 270 GB/device for it."""
    from repro.models import accounting
    b, s, d = hidden.shape
    c = min(chunk, s)
    while s % c:
        c -= 1
    n = s // c
    hs = hidden.reshape(b, n, c, d).swapaxes(0, 1)          # (n, b, c, d)
    ts = targets.reshape(b, n, c).swapaxes(0, 1)

    def body(acc, xs):
        h_c, t_c = xs
        from repro.models.matmul import pmm
        logits = _constrain_logits(
            pmm(h_c, head_w, tag="lm_head.chunked").astype(jnp.float32),
            vocab)                                          # (b, c, V)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, t_c[..., None].astype(jnp.int32),
                                   axis=-1)[..., 0]
        return acc + (logz - gold).sum(), None

    total, _ = accounting.scan(jax.checkpoint(body), jnp.zeros((), jnp.float32),
                               (hs, ts))
    return total / (b * s)


def loss_fn(params, batch: Dict[str, jax.Array], cfg: ModelConfig,
            remat: bool = True) -> jax.Array:
    kwargs = {}
    if cfg.frontend == "vision_stub":
        kwargs["prefix_embeds"] = batch["prefix_embeds"]
    if cfg.is_encoder_decoder:
        kwargs["encoder_embeds"] = batch["encoder_embeds"]
    hidden = forward(params, batch["tokens"], cfg, remat=remat,
                     return_hidden=True, **kwargs)
    if cfg.frontend == "vision_stub":
        hidden = hidden[:, -batch["tokens"].shape[1]:]   # drop prefix positions
    return chunked_xent(hidden, weight_value(lm_head_weight(params, cfg)),
                        batch["targets"], cfg.vocab)


def make_train_step(cfg: ModelConfig, opt: adamw.AdamWConfig,
                    microbatches: int = 1,
                    compress_grads: bool = False,
                    remat: bool = True) -> Callable:
    """Returns train_step(params, opt_state, comp_state, batch) ->
    (params, opt_state, comp_state, metrics)."""

    def grads_of(params, batch):
        return jax.value_and_grad(
            lambda p: loss_fn(p, batch, cfg, remat=remat))(params)

    def train_step(params, opt_state, comp_state, batch):
        if microbatches > 1:
            def split(x):
                b = x.shape[0]
                return x.reshape(microbatches, b // microbatches, *x.shape[1:])
            micro = jax.tree.map(split, batch)

            def acc_step(carry, mb):
                loss_acc, grad_acc = carry
                loss, grads = grads_of(params, mb)
                return (loss_acc + loss,
                        jax.tree.map(jnp.add, grad_acc, grads)), None

            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(acc_step, (0.0, zero), micro)
            loss = loss / microbatches
            grads = jax.tree.map(lambda g: g / microbatches, grads)
        else:
            loss, grads = grads_of(params, batch)

        if compress_grads:
            grads, comp_state = compress.apply(grads, comp_state)

        params, opt_state, metrics = adamw.apply(opt, params, grads, opt_state)
        metrics["loss"] = loss
        return params, opt_state, comp_state, metrics

    return train_step


def make_serve_step(cfg: ModelConfig) -> Callable:
    """serve_step(params, caches, tokens, position[, encoder_out]) ->
    (next_token_logits, caches) — ONE new token against the running cache
    (the brief's decode_* shapes lower this, not train_step)."""

    def serve_step(params, caches, tokens, position, encoder_out=None):
        return decode_step(params, caches, tokens, position, cfg,
                           encoder_out=encoder_out)

    return serve_step


def make_prefill(cfg: ModelConfig) -> Callable:
    """prefill(params, tokens[, extras]) -> logits — the prefill_32k graph."""

    def prefill(params, tokens, prefix_embeds=None, encoder_embeds=None):
        kwargs = {}
        if prefix_embeds is not None:
            kwargs["prefix_embeds"] = prefix_embeds
        if encoder_embeds is not None:
            kwargs["encoder_embeds"] = encoder_embeds
        return forward(params, tokens, cfg, remat=False, **kwargs)

    return prefill
