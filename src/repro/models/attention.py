"""Attention blocks: GQA/MQA (with qk-norm, arbitrary head_dim), MLA
(DeepSeek-V2 latent attention with compressed KV cache), cross-attention for
encoder-decoder stacks, and the decode path against a preallocated KV cache.

The projection matmuls are the DiT-scheduled GEMMs: on the production mesh
their sharding comes from `repro.parallel.spec_rules` (the data-layout half of
the schedule), and the contraction pattern (TP all-reduce vs split-K scatter)
is the dataflow half.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.models import shard_ctx
from repro.models.common import (ModelConfig, Params, apply_rope, dense_init,
                                 rms_head_norm, rms_norm, rope_tables)
from repro.models.matmul import pattn, pmm

NEG_INF = -1e30


def _chunk(s: int, target: int) -> int:
    """Chunk size for a length-`s` axis: the target, capped at `s`.

    The tail block is padded up to a full chunk and masked off inside
    `_flash` — chunk count stays O(s / target) for EVERY length. (The old
    rule walked down to the largest divisor of `s`, so prime or ragged
    lengths — a 4673-token VLM prefix — degraded to chunk=1 and scanned
    thousands of singleton blocks.)"""
    return max(1, min(target, s))


def gqa_params(key, cfg: ModelConfig) -> Params:
    hd = cfg.hd
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, cfg.d_model, cfg.n_heads * hd, cfg.dtype),
        "wk": dense_init(k2, cfg.d_model, cfg.n_kv_heads * hd, cfg.dtype),
        "wv": dense_init(k3, cfg.d_model, cfg.n_kv_heads * hd, cfg.dtype),
        "wo": dense_init(k4, cfg.n_heads * hd, cfg.d_model, cfg.dtype),
    }


def _flash_fwd(q, k, v, causal: bool, scale: float, cq: int, ck: int,
               sk_valid: int):
    """Streaming online-softmax forward. q: (b,nq,cq,hkv,g,d) fp32;
    k/v: (b,nk,ck,hkv,d|dv) fp32. Returns out (b,nq,cq,hkv,g,dv) and
    lse (b,nq,cq,hkv,g). Key positions >= `sk_valid` are tail padding
    (ragged lengths are padded to a full chunk) and masked off."""
    from repro.models import accounting
    b, nq, cq_, hkv, g, d = q.shape
    nk = k.shape[1]
    dv = v.shape[-1]

    def q_block(qi, q_blk):
        def kv_step(carry, inputs):
            m_run, l_run, acc = carry
            kj, k_blk, v_blk = inputs
            logits = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k_blk) * scale
            kpos = kj * ck + jnp.arange(ck)
            if causal:
                qpos = qi * cq + jnp.arange(cq)
                mask = kpos[None, :] <= qpos[:, None]
                logits = jnp.where(mask[None, None, None], logits, NEG_INF)
            if nk * ck > sk_valid:      # static: padding exists
                valid = kpos < sk_valid
                logits = jnp.where(valid[None, None, None, None, :],
                                   logits, NEG_INF)
            m_new = jnp.maximum(m_run, logits.max(-1))
            p = jnp.exp(logits - m_new[..., None])
            corr = jnp.exp(m_run - m_new)
            l_new = l_run * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum("bhgqk,bkhd->bhgqd", p, v_blk)
            return (m_new, l_new, acc), None

        m0 = jnp.full((b, hkv, g, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, cq), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, cq, dv), jnp.float32)
        (m, l, acc), _ = accounting.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nk), k.swapaxes(0, 1), v.swapaxes(0, 1)))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        # -> (b, cq, hkv, g, dv) / (b, cq, hkv, g)
        return out.transpose(0, 3, 1, 2, 4), lse.transpose(0, 3, 1, 2)

    _, (outs, lses) = accounting.scan(
        lambda c, args: (c, q_block(*args)), 0,
        (jnp.arange(nq), q.swapaxes(0, 1)))
    return outs.swapaxes(0, 1), lses.swapaxes(0, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, cq, ck, sk_valid):
    out, _ = _flash_fwd(q, k, v, causal, scale, cq, ck, sk_valid)
    return out


def _flash_vjp_fwd(q, k, v, causal, scale, cq, ck, sk_valid):
    out, lse = _flash_fwd(q, k, v, causal, scale, cq, ck, sk_valid)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, scale, cq, ck, sk_valid, res, dout):
    """Flash backward: recompute p block-by-block from lse; O(S) memory."""
    from repro.models import accounting
    q, k, v, out, lse = res
    b, nq, cq_, hkv, g, d = q.shape
    nk = k.shape[1]
    dv = v.shape[-1]
    delta = (dout * out).sum(-1)                          # (b,nq,cq,hkv,g)

    def q_block(carry, inputs):
        dk_acc, dv_acc = carry
        qi, q_blk, do_blk, lse_blk, dl_blk = inputs

        def kv_step(inner, kv_inputs):
            dq_blk, dk_acc, dv_acc = inner
            kj, k_blk, v_blk = kv_inputs
            logits = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k_blk) * scale
            kpos = kj * ck + jnp.arange(ck)
            if causal:
                qpos = qi * cq + jnp.arange(cq)
                mask = kpos[None, :] <= qpos[:, None]
                logits = jnp.where(mask[None, None, None], logits, NEG_INF)
            if nk * ck > sk_valid:      # same tail mask as the forward
                valid = kpos < sk_valid
                logits = jnp.where(valid[None, None, None, None, :],
                                   logits, NEG_INF)
            p = jnp.exp(logits - lse_blk.transpose(0, 2, 3, 1)[..., None])
            dp = jnp.einsum("bqhgd,bkhd->bhgqk", do_blk, v_blk)
            ds = p * (dp - dl_blk.transpose(0, 2, 3, 1)[..., None]) * scale
            dq_blk = dq_blk + jnp.einsum("bhgqk,bkhd->bqhgd", ds, k_blk)
            dk_new = jnp.einsum("bhgqk,bqhgd->bkhd", ds, q_blk)
            dv_new = jnp.einsum("bhgqk,bqhgd->bkhd", p, do_blk)
            dk_acc = dk_acc.at[:, kj].add(dk_new)
            dv_acc = dv_acc.at[:, kj].add(dv_new)
            return (dq_blk, dk_acc, dv_acc), None

        dq0 = jnp.zeros_like(q_blk)
        (dq_blk, dk_acc, dv_acc), _ = accounting.scan(
            kv_step, (dq0, dk_acc, dv_acc),
            (jnp.arange(nk), k.swapaxes(0, 1), v.swapaxes(0, 1)))
        return (dk_acc, dv_acc), dq_blk

    dk0 = jnp.zeros_like(k)
    dv0 = jnp.zeros_like(v)
    (dk, dv_), dqs = accounting.scan(
        q_block, (dk0, dv0),
        (jnp.arange(nq), q.swapaxes(0, 1), dout.swapaxes(0, 1),
         lse.swapaxes(0, 1), delta.swapaxes(0, 1)))
    return dqs.swapaxes(0, 1), dk, dv_


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def chunked_sdpa(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
                 chunk_q: int = 256, chunk_k: int = 256,
                 scale: Optional[float] = None) -> jax.Array:
    """Flash attention in pure jnp (custom_vjp; O(S) memory both directions):
    double scan over query/key chunks, never materializing the (Sq, Sk)
    logits. This is the memory-feasible path for 4k training and 32k prefill
    (a Pallas flash kernel plays the same role on real TPUs; this lowers
    everywhere).

    q: (B, Sq, H, D); k/v: (B, Sk, Hkv, D).
    """
    from repro.models import accounting
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    dv = v.shape[-1]
    g = h // hkv

    cq = _chunk(sq, accounting.chunk(chunk_q))
    ck = _chunk(sk, accounting.chunk(chunk_k))
    # ragged lengths (VLM prefixes make seq lengths like 4672 = 4096 + 576
    # patches, or primes) pad the tail block up to a full chunk; padded key
    # positions are masked inside _flash, padded query rows are sliced off
    nq, nk = -(-sq // cq), -(-sk // ck)
    pad_q, pad_k = nq * cq - sq, nk * ck - sk
    if scale is None:
        scale = d ** -0.5

    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v
    qc = qp.reshape(b, nq, cq, hkv, g, d).astype(jnp.float32)
    kc = kp.reshape(b, nk, ck, hkv, d).astype(jnp.float32)
    vc = vp.reshape(b, nk, ck, hkv, dv).astype(jnp.float32)
    out = _flash(qc, kc, vc, causal, scale, cq, ck, sk)
    return out.reshape(b, nq * cq, h, dv)[:, :sq].astype(q.dtype)


def _sdpa(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
          q_positions: Optional[jax.Array] = None,
          kv_len: Optional[jax.Array] = None,
          scale: Optional[float] = None, kv_order: str = "bkhd") -> jax.Array:
    """q: (B, Sq, H, D), k/v: (B, Sk, Hkv, D) with GQA head grouping, or
    (B, Hkv, Sk, D) with kv_order="bhkd", the KV cache's order, which the
    products then read as stored.

    q_positions: positions of the queries (decode: the cache write index);
    kv_len: valid cache length mask bound (decode against a preallocated cache).
    """
    b, sq, h, d = q.shape
    sk, hkv = k.shape[kv_order.index("k")], k.shape[kv_order.index("h")]
    groups = h // hkv
    qg = q.reshape(b, sq, hkv, groups, d)
    logits = jnp.einsum(f"bqhgd,{kv_order}->bhgqk", qg, k,
                        preferred_element_type=jnp.float32)
    logits *= (d ** -0.5) if scale is None else scale
    kpos = jnp.arange(sk)
    if causal:
        qpos = q_positions if q_positions is not None else jnp.arange(sq)
        mask = kpos[None, :] <= qpos[:, None]            # (sq, sk)
        logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    if kv_len is not None:
        valid = kpos[None, :] < kv_len[:, None]          # (b, sk)
        logits = jnp.where(valid[:, None, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum(f"bhgqk,{kv_order}->bqhgd", probs, v)
    return out.reshape(b, sq, h, v.shape[-1])


def _layer(stack: jax.Array, layer) -> jax.Array:
    """Layer `layer` of a stacked cache tensor, read where it lies."""
    return jax.lax.dynamic_index_in_dim(stack, layer, keepdims=False)


def _write_rows(cache: Dict[str, jax.Array], layer, rows: Dict[str, jax.Array],
                seq_axis) -> Tuple[Dict[str, jax.Array], jax.Array]:
    """Write this step's rows into layer `layer` of each stacked cache
    tensor at the layer's write index, in place: one dynamic_update_slice
    per tensor, at (layer, 0, .., index, .., 0) with the index on
    `seq_axis` of the layer (one axis for every tensor, or a dict of them
    by name). Returns the updated cache and the index."""
    def axis(name):
        return seq_axis[name] if isinstance(seq_axis, dict) else seq_axis

    idx = _layer(cache["index"], layer)
    name0, x0 = next(iter(rows.items()))
    s = x0.shape[axis(name0)]
    with jax.named_scope("kv_cache.write"):
        new = {"index": jax.lax.dynamic_update_index_in_dim(
            cache["index"], idx + s, layer, 0)}
        for name, x in rows.items():
            at = [0] * x.ndim
            at[axis(name)] = idx
            new[name] = _stored_order(jax.lax.dynamic_update_slice(
                cache[name], x[None], (layer, *at)))
    return new, idx


def _stored_order(stack: jax.Array) -> jax.Array:
    """`stack` kept in its stored, row-major order through the layer loop.

    Where no matrix unit pins the order, XLA lays a carried stack out anew
    inside the loop, at the cost of a copy of the whole stack on each side
    of it: MHA decode's attention is a batch of matrix-vector products,
    which XLA computes as multiply-reduces, and the one-row write then
    prefers the sequence axis major. The SPMD partitioner gathers the
    operand of a layout constraint whole onto every device, so a program
    partitioned over an activation mesh goes without it."""
    if shard_ctx.get_mesh() is not None:
        return stack
    return with_layout_constraint(stack, Layout(tuple(range(stack.ndim))))


def gqa_attention(p: Params, x: jax.Array, cfg: ModelConfig,
                  positions: jax.Array,
                  cache: Optional[Dict[str, jax.Array]] = None,
                  causal: bool = True,
                  kv_input: Optional[jax.Array] = None,
                  layer=0,
                  ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Self- or cross-attention. With `cache` (a stack of layers, see
    `make_kv_cache`), runs one decode step of layer `layer`: writes this
    step's K/V into that layer at its `cache['index']` and attends to the
    prefix; returns the updated stack.
    kv_input: encoder output for cross-attention (no cache update then unless
    it is the first step)."""
    if cache is not None and kv_input is not None:
        # the decode branch would silently write the encoder output into the
        # self-attention cache and RoPE it — no caller means that
        raise ValueError("gqa_attention: cache and kv_input are mutually "
                         "exclusive (cached cross-attention is not "
                         "supported; precompute encoder K/V instead)")
    b, s, _ = x.shape
    hd = cfg.hd
    kv_src = kv_input if kv_input is not None else x
    q = pmm(x, p["wq"], tag="attn.q").reshape(b, s, cfg.n_heads, hd)
    k = pmm(kv_src, p["wk"], tag="attn.k").reshape(
        b, kv_src.shape[1], cfg.n_kv_heads, hd)
    v = pmm(kv_src, p["wv"], tag="attn.v").reshape(
        b, kv_src.shape[1], cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q, k = rms_head_norm(q), rms_head_norm(k)
    if kv_input is None:  # RoPE only for self-attention
        cos_q, sin_q = rope_tables(positions, hd, cfg.rope_theta)
        q = apply_rope(q, cos_q, sin_q)
        k = apply_rope(k, cos_q, sin_q)

    if cache is None:
        self_causal = causal and kv_input is None
        if s > 1024 and kv_src.shape[1] > 1024:
            unfused = lambda: chunked_sdpa(q, k, v, causal=self_causal)
        else:
            unfused = lambda: _sdpa(q, k, v, causal=self_causal)
        out = pattn(q, k, v, causal=self_causal, tag="attn.sdpa",
                    unfused=unfused)
        new_cache = None
    else:
        new_cache, idx = _write_rows(
            cache, layer, {"k": k.swapaxes(1, 2), "v": v.swapaxes(1, 2)},
            seq_axis=2)
        ck, cv = _layer(new_cache["k"], layer), _layer(new_cache["v"], layer)
        kv_len = jnp.full((b,), idx + s, dtype=jnp.int32)
        # thread the caller's causal flag — hard-coding True here broke
        # non-causal decode (prefix-LM scoring attends to the whole prefix).
        # pattn's contract is (b, skv, hkv, d): the routed flat_attention
        # gets a transposed view of the layer, which XLA materialises; the
        # unfused path reads the cache's (b, hkv, skv, d) as stored.
        out = pattn(q, ck.swapaxes(1, 2), cv.swapaxes(1, 2), causal=causal,
                    q_positions=positions, kv_len=kv_len, tag="attn.decode",
                    unfused=lambda: _sdpa(q, ck, cv, causal=causal,
                                          q_positions=positions,
                                          kv_len=kv_len, kv_order="bhkd"))
    return pmm(out.reshape(b, s, cfg.n_heads * hd), p["wo"],
               tag="attn.o"), new_cache


# ---------------------------------------------------------------------------
# MLA — DeepSeek-V2 Multi-head Latent Attention
# ---------------------------------------------------------------------------

def mla_params(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 8)
    dn, dr = cfg.nope_head_dim, cfg.rope_head_dim
    p = {
        "w_dkv": dense_init(ks[0], cfg.d_model, cfg.kv_lora_rank, cfg.dtype),
        "w_kr": dense_init(ks[1], cfg.d_model, dr, cfg.dtype),
        "w_uk": dense_init(ks[2], cfg.kv_lora_rank, cfg.n_heads * dn, cfg.dtype),
        "w_uv": dense_init(ks[3], cfg.kv_lora_rank, cfg.n_heads * dn, cfg.dtype),
        "wo": dense_init(ks[4], cfg.n_heads * dn, cfg.d_model, cfg.dtype),
    }
    p["kv_norm"] = {"scale": jnp.ones((cfg.kv_lora_rank,), jnp.float32)}
    if cfg.q_lora_rank:
        p["w_dq"] = dense_init(ks[5], cfg.d_model, cfg.q_lora_rank, cfg.dtype)
        p["q_norm"] = {"scale": jnp.ones((cfg.q_lora_rank,), jnp.float32)}
        p["w_uq"] = dense_init(ks[6], cfg.q_lora_rank,
                               cfg.n_heads * (dn + dr), cfg.dtype)
    else:
        p["wq"] = dense_init(ks[7], cfg.d_model, cfg.n_heads * (dn + dr), cfg.dtype)
    return p


def _mla_scale(cfg: ModelConfig) -> float:
    """The softmax scale: (dn + dr)^-1/2, times YaRN's mscale^2."""
    scale = (cfg.nope_head_dim + cfg.rope_head_dim) ** -0.5
    if cfg.rope_scaling is not None:
        scale *= cfg.rope_scaling.softmax_factor()
    return scale


def _latent_sdpa(q_lat, q_rope, c_kv, k_rope_t, q_positions, kv_len,
                 scale):
    """Absorbed MLA attention against one layer of the latent cache as
    stored: scores are q_lat . c_kv + q_rope . k_rope, the values c_kv
    itself. q_lat (b, sq, h, r), q_rope (b, sq, h, dr); c_kv (b, sk, r),
    and the rotary key transposed, k_rope_t (b, dr, sk). Each product is
    one batched matrix product over the sq * h query rows of a sequence,
    and reads the layer where it lies, with no concatenated (b, sk, r + dr)
    copy of it."""
    b, sq, h, r = q_lat.shape
    sk = c_kv.shape[1]
    rows = (b, sq * h)
    logits = (jnp.einsum("bmr,bkr->bmk", q_lat.reshape(*rows, r), c_kv,
                         preferred_element_type=jnp.float32)
              + jnp.einsum("bmd,bdk->bmk", q_rope.reshape(*rows, -1),
                           k_rope_t, preferred_element_type=jnp.float32))
    logits = logits.reshape(b, sq, h, sk) * scale
    kpos = jnp.arange(sk)
    mask = ((kpos[None, :] <= q_positions[:, None])
            & (kpos < kv_len)[None, :])                     # (sq, sk)
    logits = jnp.where(mask[None, :, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(c_kv.dtype)
    out = jnp.einsum("bmk,bkr->bmr", probs.reshape(*rows, sk), c_kv)
    return out.reshape(b, sq, h, r)


def mla_attention(p: Params, x: jax.Array, cfg: ModelConfig,
                  positions: jax.Array,
                  cache: Optional[Dict[str, jax.Array]] = None,
                  layer=0,
                  ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """MLA. The query's and the KV's latents are RMS-normed after their
    down-projections, and the normed KV latent is what is cached; the
    rotary part uses the configuration's `rope_scaling` (YaRN), whose
    mscale^2 also scales the softmax. Two execution forms, as in
    DeepSeek-V2's own deployment:

    - train/prefill (no cache): the NAIVE form — up-project K/V from c_kv and
      run flash attention at head dim (dn + dr). Projection FLOPs are
      identical to the absorbed form but scores cost (dn+dr) instead of
      (r+dr) per head — 3x cheaper for the paper config.
    - decode (cache): the ABSORBED form — W_uk folds into the query so
      attention runs in latent space against the compressed c_kv directly (an
      MQA with key dim r + dr). Only c_kv and the shared rotary key are
      cached, and no per-head K/V is ever rematerialized — the flat decode
      GEMMs of paper Insight 4."""
    b, s, _ = x.shape
    dn, dr, h, r = cfg.nope_head_dim, cfg.rope_head_dim, cfg.n_heads, cfg.kv_lora_rank

    if cfg.q_lora_rank:
        q_lat = pmm(x, p["w_dq"], tag="mla.q_down")
        with jax.named_scope("norm"):
            q_lat = rms_norm(p["q_norm"], q_lat)
        q = pmm(q_lat, p["w_uq"], tag="mla.q_up")
    else:
        q = pmm(x, p["wq"], tag="mla.q")
    q = q.reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]

    c_kv = pmm(x, p["w_dkv"], tag="mla.kv_down")           # (b, s, r)
    with jax.named_scope("norm"):
        c_kv = rms_norm(p["kv_norm"], c_kv)
    k_r = pmm(x, p["w_kr"], tag="mla.k_rope").reshape(b, s, 1, dr)

    cos, sin = rope_tables(positions, dr, cfg.rope_theta, cfg.rope_scaling)
    q_rope = apply_rope(q_rope, cos, sin)
    k_r = apply_rope(k_r, cos, sin)

    scale = _mla_scale(cfg)
    if cache is None:
        # naive form: up-project K/V once, flash attention at dim dn + dr.
        sk = c_kv.shape[1]
        k_nope = pmm(c_kv, p["w_uk"], tag="mla.k_up").reshape(b, sk, h, dn)
        v = pmm(c_kv, p["w_uv"], tag="mla.v_up").reshape(b, sk, h, dn)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_r, (b, sk, h, dr))], axis=-1)
        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        if s > 1024:
            unfused = lambda: chunked_sdpa(q_full, k_full, v, causal=True,
                                           scale=scale)
        else:
            unfused = lambda: _sdpa(q_full, k_full, v, causal=True,
                                    scale=scale)
        out = pattn(q_full, k_full, v, causal=True, scale=scale,
                    tag="mla.sdpa", unfused=unfused)
        out = out.reshape(b, s, h * dn)
        return pmm(out, p["wo"], tag="mla.o"), None

    # absorbed form (decode): q_lat[h] = q_nope[h] @ W_uk[h]^T  (b,s,h,r)
    # per-head batched contraction, not a single dense GEMM — stays einsum
    # but is logged so the observed workload covers the absorbed path. The
    # einsum is n_heads independent (b*s, r, dn) contractions, so count=h —
    # a single record undercounted the absorbed decode workload ~h×
    from repro.models.matmul import record_gemm
    record_gemm("mla.q_absorb", b * s, r, dn, count=h)
    w_uk = p["w_uk"].reshape(r, h, dn)
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_uk)
    new_cache, idx = _write_rows(
        cache, layer, {"c_kv": c_kv, "k_rope_t": k_r[:, :, 0].swapaxes(1, 2)},
        seq_axis={"c_kv": 1, "k_rope_t": 2})
    c_kv = _layer(new_cache["c_kv"], layer)                # (b, sk, r)
    k_rt = _layer(new_cache["k_rope_t"], layer)            # (b, dr, sk)
    kv_len = idx + s

    def latent():
        return _latent_sdpa(q_lat, q_rope, c_kv, k_rt, positions, kv_len,
                            scale)

    # the fused executor's MQA view of the layer (key dim r + dr): a
    # concatenated copy of the latent cache, which `latent` never reads
    q_aug = jnp.concatenate([q_lat, q_rope], axis=-1)      # (b,s,h,r+dr)
    k_aug = jnp.concatenate([c_kv, k_rt.swapaxes(1, 2)],
                            axis=-1)[:, :, None, :]        # (b,sk,1,r+dr)
    v_lat = c_kv[:, :, None, :]                            # (b,sk,1,r)
    kv_len_b = jnp.full((b,), kv_len, jnp.int32)
    o_lat = pattn(q_aug, k_aug, v_lat, causal=True, q_positions=positions,
                  kv_len=kv_len_b, scale=scale, tag="mla.decode",
                  unfused=latent, kv_copy=True)
    # un-absorb the values: out[h] = o_lat @ W_uv[h] — again h per-head
    # (b*s, dn, r) contractions in one einsum
    record_gemm("mla.v_unabsorb", b * s, dn, r, count=h)
    w_uv = p["w_uv"].reshape(r, h, dn)
    out = jnp.einsum("bshr,rhd->bshd", o_lat, w_uv).reshape(b, s, h * dn)
    return pmm(out, p["wo"], tag="mla.o"), new_cache


def make_kv_cache(cfg: ModelConfig, n_layers: int, batch: int,
                  max_len: int) -> Dict[str, jax.Array]:
    """Preallocated cache of a stack of `n_layers` attention layers, with
    each layer's write position in `index`. GQA K/V are (L, B, Hkv, S, D),
    the order the attention products read, so a decode step reads a layer
    where it lies and writes its rows in place. MLA caches the normed
    latent (L, B, S, r) and the shared rotary key transposed,
    (L, B, dr, S): the order the score product reads a 64-wide key in,
    which XLA would otherwise relay around the layer loop."""
    if cfg.attn == "mla":
        return {
            "c_kv": jnp.zeros((n_layers, batch, max_len, cfg.kv_lora_rank),
                              cfg.dtype),
            "k_rope_t": jnp.zeros((n_layers, batch, cfg.rope_head_dim,
                                   max_len), cfg.dtype),
            "index": jnp.zeros((n_layers,), jnp.int32),
        }
    kv = (n_layers, batch, cfg.n_kv_heads, max_len, cfg.hd)
    return {
        "k": jnp.zeros(kv, cfg.dtype),
        "v": jnp.zeros(kv, cfg.dtype),
        "index": jnp.zeros((n_layers,), jnp.int32),
    }
