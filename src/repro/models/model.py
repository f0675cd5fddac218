"""Top-level model assembly for all assigned architectures.

A model is a stack of homogeneous layer *groups* so parameters stack cleanly
and each group lowers as ONE `jax.lax.scan` (small HLO, fast SPMD partitioning
on the 512-device dry-run):

- dense / qk-norm / MQA / VLM archs: one group of attention blocks
- MoE archs: leading dense layers unrolled + one scanned MoE group
- zamba2: scanned super-layers of (hybrid_attn_every mamba2 blocks + one
  SHARED attention block — same weights every super-layer, as in the paper)
- xlstm: scanned super-layers of (slstm_every-1 mLSTM + 1 sLSTM)
- seamless (enc-dec): scanned encoder group + scanned decoder group with
  cross-attention to the (stub-)frontend encoder output

Entry points: init_params / forward (logits) / decode_init + decode_step
(one-token serve step against preallocated caches).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import accounting
from repro.models import shard_ctx
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm
from repro.models.common import (ModelConfig, Params, apply_mlp, apply_norm,
                                 dense_init, mlp_params, norm_params)
from repro.models.matmul import StoredWeight, pmm


# ---------------------------------------------------------------------------
# single blocks
# ---------------------------------------------------------------------------

def _attn_block_params(key, cfg: ModelConfig, with_mlp: bool = True,
                       cross: bool = False) -> Params:
    ks = jax.random.split(key, 5)
    p = {
        "ln1": norm_params(cfg),
        "attn": attn.mla_params(ks[0], cfg) if cfg.attn == "mla"
        else attn.gqa_params(ks[0], cfg),
    }
    if cross:
        p["ln_x"] = norm_params(cfg)
        p["xattn"] = attn.gqa_params(ks[2], cfg)
    if with_mlp:
        p["ln2"] = norm_params(cfg)
        p["mlp"] = mlp_params(ks[1], cfg)
    return p


def _attn_block(p: Params, x, cfg: ModelConfig, positions, cache=None,
                enc_out=None, causal=True, layer=0):
    h = apply_norm(p["ln1"], x, cfg)
    if cfg.attn == "mla":
        a, new_cache = attn.mla_attention(p["attn"], h, cfg, positions, cache,
                                          layer=layer)
    else:
        a, new_cache = attn.gqa_attention(p["attn"], h, cfg, positions, cache,
                                          causal=causal, layer=layer)
    x = x + a
    if enc_out is not None:
        h = apply_norm(p["ln_x"], x, cfg)
        a, _ = attn.gqa_attention(p["xattn"], h, cfg, positions, None,
                                  causal=False, kv_input=enc_out)
        x = x + a
    if "mlp" in p:
        h = apply_norm(p["ln2"], x, cfg)
        x = x + apply_mlp(p["mlp"], h, cfg)
    return x, new_cache


def _moe_block_params(key, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    p = _attn_block_params(k1, cfg, with_mlp=False)
    p["ln2"] = norm_params(cfg)
    p["moe"] = moe_lib.moe_params(k2, cfg)
    return p


def _moe_block(p: Params, x, cfg: ModelConfig, positions, cache=None,
               layer=0):
    x, new_cache = _attn_block(p, x, cfg, positions, cache, layer=layer)
    h = apply_norm(p["ln2"], x, cfg)
    x = x + moe_lib.apply_moe(p["moe"], h, cfg)
    return x, new_cache


def _mamba_block_params(key, cfg: ModelConfig) -> Params:
    return {"ln": norm_params(cfg), "mixer": ssm.mamba2_params(key, cfg)}


def _mamba_block(p: Params, x, cfg: ModelConfig, state=None):
    h = apply_norm(p["ln"], x, cfg)
    y, new_state = ssm.mamba2_mixer(p["mixer"], h, cfg, state)
    return x + y, new_state


def _mlstm_block_params(key, cfg: ModelConfig) -> Params:
    return {"ln": norm_params(cfg), "mixer": ssm.mlstm_params(key, cfg)}


def _mlstm_block(p: Params, x, cfg: ModelConfig, state=None):
    h = apply_norm(p["ln"], x, cfg)
    y, new_state = ssm.mlstm_mixer(p["mixer"], h, cfg, state)
    return x + y, new_state


def _slstm_block_params(key, cfg: ModelConfig) -> Params:
    return {"ln": norm_params(cfg), "mixer": ssm.slstm_params(key, cfg)}


def _slstm_block(p: Params, x, cfg: ModelConfig, state=None):
    h = apply_norm(p["ln"], x, cfg)
    y, new_state = ssm.slstm_mixer(p["mixer"], h, cfg, state)
    return x + y, new_state


# ---------------------------------------------------------------------------
# layer-group structure per architecture family
# ---------------------------------------------------------------------------

def _stack(key, n: int, make_fn) -> Params:
    """Stack n block-param pytrees along a new leading axis (scan format)."""
    keys = jax.random.split(key, n)
    blocks = [make_fn(k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)


def init_params(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 8)
    p: Params = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab, cfg.d_model), jnp.float32)
                  * cfg.d_model ** -0.5).astype(cfg.dtype),
        "ln_f": norm_params(cfg),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[6], cfg.d_model, cfg.vocab, cfg.dtype)

    if cfg.frontend in ("vision_stub", "audio_stub"):
        # frontend stub: a learned projection applied to precomputed
        # patch/frame embeddings supplied by input_specs()
        p["frontend_proj"] = dense_init(ks[7], cfg.d_model, cfg.d_model, cfg.dtype)

    if cfg.is_encoder_decoder:
        p["encoder"] = _stack(ks[1], cfg.n_encoder_layers,
                              lambda k: _attn_block_params(k, cfg))
        p["decoder"] = _stack(ks[2], cfg.n_layers,
                              lambda k: _attn_block_params(k, cfg, cross=True))
        p["ln_enc"] = norm_params(cfg)
        return p

    if cfg.block_pattern == "attn":
        if cfg.n_experts:
            if cfg.n_dense_layers:
                p["dense_layers"] = _stack(ks[1], cfg.n_dense_layers,
                                           lambda k: _attn_block_params(k, cfg))
            p["moe_layers"] = _stack(ks[2], cfg.n_layers - cfg.n_dense_layers,
                                     lambda k: _moe_block_params(k, cfg))
        else:
            p["layers"] = _stack(ks[1], cfg.n_layers,
                                 lambda k: _attn_block_params(k, cfg))
    elif cfg.block_pattern == "mamba2_hybrid":
        per = cfg.hybrid_attn_every
        n_super, rem = divmod(cfg.n_layers, per)
        p["mamba_layers"] = _stack(ks[1], n_super * per,
                                   lambda k: _mamba_block_params(k, cfg))
        if rem:
            p["mamba_tail"] = _stack(ks[3], rem,
                                     lambda k: _mamba_block_params(k, cfg))
        # ONE shared attention block reused after every super-layer (zamba2)
        p["shared_attn"] = _attn_block_params(ks[2], cfg)
    elif cfg.block_pattern == "xlstm":
        per = cfg.slstm_every
        assert cfg.n_layers % per == 0, "xlstm layers must divide by slstm_every"
        n_super = cfg.n_layers // per
        p["mlstm_layers"] = _stack(ks[1], n_super * (per - 1),
                                   lambda k: _mlstm_block_params(k, cfg))
        p["slstm_layers"] = _stack(ks[2], n_super,
                                   lambda k: _slstm_block_params(k, cfg))
    else:
        raise ValueError(cfg.block_pattern)
    return p


# ---------------------------------------------------------------------------
# scanned group application
# ---------------------------------------------------------------------------

def _scan_group(stacked: Params, x, fn, remat: bool = True):
    """Run a stacked layer group as lax.scan over the leading axis (python
    loop in accounting mode so cost_analysis sees every layer)."""
    def pinned(layer_params, carry):
        with jax.named_scope("block"):
            return shard_ctx.constrain_tokens(fn(layer_params, carry))

    body = pinned
    if remat:
        body = jax.checkpoint(pinned)

    if accounting.UNROLL_LAYERS:
        n = jax.tree.leaves(stacked)[0].shape[0]
        for i in range(n):
            x = body(jax.tree.map(lambda a: a[i], stacked), x)
        return x

    def step(carry, layer_params):
        out = body(layer_params, carry)
        return out, None

    x, _ = jax.lax.scan(step, x, stacked)
    return x


def forward(params: Params, tokens: jax.Array, cfg: ModelConfig,
            prefix_embeds: Optional[jax.Array] = None,
            encoder_embeds: Optional[jax.Array] = None,
            remat: bool = True, return_hidden: bool = False) -> jax.Array:
    """Training/prefill forward -> logits (B, S, vocab), or the final hidden
    states (B, S, D) with return_hidden=True (the fused-CE loss path computes
    vocab projections chunk-by-chunk to avoid materializing fp32 logits).

    prefix_embeds: VLM/audio stub frontend output prepended to the sequence.
    encoder_embeds: enc-dec source-side embeddings (audio frames).
    """
    with jax.named_scope("embed"):
        x = shard_ctx.constrain_tokens(
            jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype))
        if prefix_embeds is not None:
            pe = pmm(prefix_embeds.astype(cfg.dtype), params["frontend_proj"],
                     tag="frontend.proj")
            x = jnp.concatenate([pe, x], axis=1)
    b, s, _ = x.shape
    positions = jnp.arange(s)

    if cfg.is_encoder_decoder:
        with jax.named_scope("embed"):
            enc = pmm(encoder_embeds.astype(cfg.dtype),
                      params["frontend_proj"], tag="frontend.proj")
        enc = _scan_group(
            params["encoder"], enc,
            lambda p, h: _attn_block(p, h, cfg, jnp.arange(enc.shape[1]),
                                     causal=False)[0], remat)
        with jax.named_scope("final_norm"):
            enc = apply_norm(params["ln_enc"], enc, cfg)
        x = _scan_group(
            params["decoder"], x,
            lambda p, h: _attn_block(p, h, cfg, positions, enc_out=enc)[0],
            remat)
    elif cfg.block_pattern == "attn":
        if cfg.n_experts:
            if cfg.n_dense_layers:
                x = _scan_group(params["dense_layers"], x,
                                lambda p, h: _attn_block(p, h, cfg, positions)[0],
                                remat)
            x = _scan_group(params["moe_layers"], x,
                            lambda p, h: _moe_block(p, h, cfg, positions)[0],
                            remat)
        else:
            x = _scan_group(params["layers"], x,
                            lambda p, h: _attn_block(p, h, cfg, positions)[0],
                            remat)
    elif cfg.block_pattern == "mamba2_hybrid":
        per = cfg.hybrid_attn_every
        n_super = jax.tree.leaves(params["mamba_layers"])[0].shape[0] // per
        # reshape stacked mamba params to (n_super, per, ...)
        grouped = jax.tree.map(
            lambda a: a.reshape(n_super, per, *a.shape[1:]),
            params["mamba_layers"])

        def super_layer(p_super, h):
            def inner(pp, hh):
                return _mamba_block(pp, hh, cfg)[0], None
            h, _ = accounting.scan(lambda c, pp: inner(pp, c), h, p_super)
            h, _ = _attn_block(params["shared_attn"], h, cfg, positions)
            return h

        x = _scan_group(grouped, x, super_layer, remat)
        if "mamba_tail" in params:
            x = _scan_group(params["mamba_tail"], x,
                            lambda p, h: _mamba_block(p, h, cfg)[0], remat)
    elif cfg.block_pattern == "xlstm":
        per = cfg.slstm_every
        n_super = jax.tree.leaves(params["slstm_layers"])[0].shape[0]
        grouped_m = jax.tree.map(
            lambda a: a.reshape(n_super, per - 1, *a.shape[1:]),
            params["mlstm_layers"])

        def super_layer(p_super, h):
            pm, psl = p_super
            h, _ = accounting.scan(lambda c, pp: (_mlstm_block(pp, c, cfg)[0], None),
                                  h, pm)
            h = _slstm_block(psl, h, cfg)[0]
            return h

        x = _scan_group((grouped_m, params["slstm_layers"]), x, super_layer, remat)
    else:
        raise ValueError(cfg.block_pattern)

    with jax.named_scope("final_norm"):
        x = apply_norm(params["ln_f"], x, cfg)
    if return_hidden:
        return x
    # tied-embedding logits: x @ embed.T is the same dot_general the einsum
    # lowered to, expressed as a routable dense GEMM (lm_head_weight hands
    # the tied case over as the embedding, stored transposed)
    with jax.named_scope("lm_head"):
        logits = pmm(x, lm_head_weight(params, cfg), tag="lm_head")
        return logits.astype(jnp.float32)


def lm_head_weight(params: Params, cfg: ModelConfig):
    """The (D, vocab) LM head: the untied `lm_head`, or the tied embedding
    (vocab, D) as it is stored, marked as the transpose of what the head
    multiplies by (`StoredWeight`), so that a kernel reads it in place.
    A caller that needs the matrix takes `matmul.weight_value`."""
    head = params.get("lm_head")
    if head is None:
        return StoredWeight(params["embed"].astype(cfg.dtype),
                            transposed=True)
    return head


# ---------------------------------------------------------------------------
# decode (serve) path — one scan over each layer group. Its stacked KV cache
# rides in the carry beside the layer counter, so each layer reads its K/V
# where they lie and writes its rows in place. Its GEMM weights stay out of
# the scan's xs: each layer's GEMMs get their stack and the layer counter
# (`StoredWeight`) and read the layer where it lies. The small leaves (norms,
# biases, the router, MLA's w_uk / w_uv that XLA's einsums read) are the
# xs. Recurrent states (mamba2, xLSTM), KB to MB a layer, stay in xs/ys.
# ---------------------------------------------------------------------------

# stacked leaves that are matrices a layer reads outside pmm and the grouped
# expert GEMMs, or too narrow for a kernel block (the router's 160 columns)
_SLICED_WEIGHTS = ("router", "w_uk", "w_uv")


def _by_reference(path, leaf) -> bool:
    """Whether the layer loop hands a stacked leaf to its layer as a
    `StoredWeight`: the matrices its GEMMs read, (L, K, N) or the experts'
    (L, E, K, N). Under an activation mesh every leaf is sliced: its
    partitioned GEMMs and the capacity dispatch read whole arrays."""
    return (shard_ctx.get_mesh() is None and leaf.ndim >= 3
            and getattr(path[-1], "key", None) not in _SLICED_WEIGHTS)


def decode_init(params: Params, cfg: ModelConfig, batch: int,
                max_len: int) -> Dict[str, Any]:
    """Preallocated cache/state pytree for one-token decode steps."""
    def stack_states(n, make):
        return jax.tree.map(lambda *xs: jnp.stack(xs), *[make() for _ in range(n)])

    def kv(n):
        return attn.make_kv_cache(cfg, n, batch, max_len)

    caches: Dict[str, Any] = {}
    if cfg.is_encoder_decoder:
        caches["decoder"] = kv(cfg.n_layers)
        return caches
    if cfg.block_pattern == "attn":
        n_dense = cfg.n_dense_layers if cfg.n_experts else 0
        if cfg.n_experts:
            if n_dense:
                caches["dense"] = kv(n_dense)
            caches["moe"] = kv(cfg.n_layers - n_dense)
        else:
            caches["layers"] = kv(cfg.n_layers)
    elif cfg.block_pattern == "mamba2_hybrid":
        per = cfg.hybrid_attn_every
        n_super, rem = divmod(cfg.n_layers, per)
        caches["mamba"] = stack_states(n_super * per,
                                       lambda: ssm.mamba2_state(cfg, batch))
        if rem:
            caches["mamba_tail"] = stack_states(
                rem, lambda: ssm.mamba2_state(cfg, batch))
        caches["shared_attn"] = kv(n_super)
    elif cfg.block_pattern == "xlstm":
        per = cfg.slstm_every
        n_super = cfg.n_layers // per
        caches["mlstm"] = stack_states(n_super * (per - 1),
                                       lambda: ssm.mlstm_state(cfg, batch))
        caches["slstm"] = stack_states(n_super,
                                       lambda: ssm.slstm_state(cfg, batch))
    return caches


def decode_step(params: Params, caches: Dict[str, Any], tokens: jax.Array,
                position: jax.Array, cfg: ModelConfig,
                encoder_out: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Dict[str, Any]]:
    """One decode step: tokens (B, 1) -> logits (B, vocab), updated caches.

    Every op the step emits sits under one top-level `jax.named_scope`
    (`embed`, `block`, `final_norm`, `lm_head`), except the layer scan's
    own slicing of its small stacked leaves (and of recurrent states),
    which stays outside `block` so a device profile tells the plumbing from
    the layers (docs/observability.md). KV caches are carried, not sliced:
    their only writes are the in-place ones under `kv_cache.write`. GEMM
    weights are not sliced either: each layer's GEMMs read their layer of
    the stack where it lies."""
    with jax.named_scope("embed"):
        x = shard_ctx.constrain_tokens(
            jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype))
    positions = position[None] if position.ndim == 0 else position

    def block(fn, *args):
        with jax.named_scope("block"):
            return fn(*args)

    def scan_kv_layers(stacked_params, cache, x, block_fn):
        """block_fn(layer_params, h, cache, layer) -> (h, cache)."""
        if accounting.UNROLL_LAYERS:
            n = jax.tree.leaves(stacked_params)[0].shape[0]
            for i in range(n):
                x, cache = block(block_fn,
                                 jax.tree.map(lambda a: a[i], stacked_params),
                                 x, cache, i)
            return x, cache

        flat, tree = jax.tree_util.tree_flatten_with_path(stacked_params)
        by_ref = [_by_reference(path, leaf) for path, leaf in flat]
        xs = [None if r else leaf for r, (_, leaf) in zip(by_ref, flat)]

        def step(carry, lp):
            h, layer, c = carry
            lp = jax.tree_util.tree_unflatten(tree, [
                StoredWeight(leaf, layer) if r else sliced
                for r, (_, leaf), sliced in zip(by_ref, flat, lp)])
            h, c = block(block_fn, lp, h, c, layer)
            return (h, layer + 1, c), None
        (x, _, cache), _ = jax.lax.scan(
            step, (x, jnp.zeros((), jnp.int32), cache), xs,
            length=flat[0][1].shape[0])
        return x, cache

    new_caches = dict(caches)
    if cfg.is_encoder_decoder:
        x, new_caches["decoder"] = scan_kv_layers(
            params["decoder"], caches["decoder"], x,
            lambda lp, h, c, l: _attn_block(lp, h, cfg, positions, cache=c,
                                            enc_out=encoder_out, layer=l))
    elif cfg.block_pattern == "attn":
        if cfg.n_experts:
            if cfg.n_dense_layers:
                x, new_caches["dense"] = scan_kv_layers(
                    params["dense_layers"], caches["dense"], x,
                    lambda lp, h, c, l: _attn_block(lp, h, cfg, positions,
                                                    cache=c, layer=l))
            x, new_caches["moe"] = scan_kv_layers(
                params["moe_layers"], caches["moe"], x,
                lambda lp, h, c, l: _moe_block(lp, h, cfg, positions,
                                               cache=c, layer=l))
        else:
            x, new_caches["layers"] = scan_kv_layers(
                params["layers"], caches["layers"], x,
                lambda lp, h, c, l: _attn_block(lp, h, cfg, positions,
                                                cache=c, layer=l))
    elif cfg.block_pattern == "mamba2_hybrid":
        per = cfg.hybrid_attn_every
        n_super = jax.tree.leaves(caches["shared_attn"])[0].shape[0]
        grouped_p = jax.tree.map(
            lambda a: a.reshape(n_super, per, *a.shape[1:]), params["mamba_layers"])
        grouped_c = jax.tree.map(
            lambda a: a.reshape(n_super, per, *a.shape[1:]), caches["mamba"])

        def super_step(carry, pc):
            h, layer, ca = carry
            pm, cm = pc

            def inner(c, pcc):
                pp, cc = pcc
                out, nc = _mamba_block(pp, c, cfg, state=cc)
                return out, nc
            with jax.named_scope("block"):
                h, new_cm = accounting.scan(inner, h, (pm, cm))
                h, ca = _attn_block(params["shared_attn"], h, cfg,
                                    positions, cache=ca, layer=layer)
            return (h, layer + 1, ca), new_cm

        (x, _, new_caches["shared_attn"]), new_cm = accounting.scan(
            super_step, (x, jnp.zeros((), jnp.int32), caches["shared_attn"]),
            (grouped_p, grouped_c))
        new_caches["mamba"] = jax.tree.map(
            lambda a: a.reshape(n_super * per, *a.shape[2:]), new_cm)
        if "mamba_tail" in params:
            x, new_caches["mamba_tail"] = accounting.scan(
                lambda h, ps: block(_mamba_block, ps[0], h, cfg, ps[1]),
                x, (params["mamba_tail"], caches["mamba_tail"]))
    elif cfg.block_pattern == "xlstm":
        per = cfg.slstm_every
        n_super = jax.tree.leaves(caches["slstm"])[0].shape[0]
        grouped_p = jax.tree.map(
            lambda a: a.reshape(n_super, per - 1, *a.shape[1:]),
            params["mlstm_layers"])
        grouped_c = jax.tree.map(
            lambda a: a.reshape(n_super, per - 1, *a.shape[1:]), caches["mlstm"])

        def super_step(carry, pc):
            h = carry
            (pm, cm), (psl, csl) = pc

            def inner(c, pcc):
                pp, cc = pcc
                out, nc = _mlstm_block(pp, c, cfg, state=cc)
                return out, nc
            with jax.named_scope("block"):
                h, new_cm = accounting.scan(inner, h, (pm, cm))
                h, new_csl = _slstm_block(psl, h, cfg, state=csl)
            return h, (new_cm, new_csl)

        x, (new_cm, new_csl) = accounting.scan(
            super_step, x,
            ((grouped_p, grouped_c), (params["slstm_layers"], caches["slstm"])))
        new_caches["mlstm"] = jax.tree.map(
            lambda a: a.reshape(n_super * (per - 1), *a.shape[2:]), new_cm)
        new_caches["slstm"] = new_csl

    with jax.named_scope("final_norm"):
        x = apply_norm(params["ln_f"], x, cfg)
    with jax.named_scope("lm_head"):
        logits = pmm(x, lm_head_weight(params, cfg), tag="lm_head")
        logits = logits[:, -1].astype(jnp.float32)
    return logits, new_caches
