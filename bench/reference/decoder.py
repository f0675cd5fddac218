"""Plain float32 forward of a dense decoder, for the served-token check.

Written from the published architecture in `jax.numpy`, with no kernel,
cache or batching, and importing nothing of the program. Every matrix
product runs at `Precision.HIGHEST`: on a TPU a float32 product otherwise
runs in bf16 passes. The weights are the canonical ones of `bench.weights`,
made again from the run's seed.

Architecture (both configurations): token embedding; per layer a pre-norm
(non-parametric LayerNorm, or RMSNorm with a scale), grouped-query
attention with RoPE on the whole head (rotate-half form, theta from the
configuration), causal softmax, an output projection and a residual; then
a pre-norm SwiGLU MLP and a residual; a final norm and the tied LM head
(logits = h @ embed^T). Departures from the published models are listed
under `assumed` in each configuration file.

`control=True` computes the same forward with every GEMM operand rounded to
float8 (e4m3, one scale per weight matrix and per activation row): the
precision below the bf16 the configurations state, which the check has to
fail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.flops import Dims

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
F8_MAX = 448.0


def _fp8(x, axis):
    """Round to float8 e4m3 with an absmax scale over `axis`."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = F8_MAX / jnp.maximum(amax, 1e-30)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _mm(x, w, control):
    """x (T, K) @ w (K, N) in float32."""
    w = w.astype(jnp.float32)
    if control:
        x, w = _fp8(x, -1), _fp8(w, None)
    return jnp.dot(x, w, precision=HI)


def _norm(x, scale, kind, eps):
    if kind == "rmsnorm":
        ms = jnp.mean(x * x, -1, keepdims=True)
        return x * jax.lax.rsqrt(ms + eps) * scale
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rope(x, theta):
    """x (T, heads, hd): rotate-half RoPE at positions 0..T-1."""
    t, _, hd = x.shape
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _attention(q, k, v, dims: Dims, control):
    """Causal GQA in query blocks, so the scores of a long sequence fit."""
    t = q.shape[0]
    g = dims.heads // dims.kv_heads
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    if control:
        k, v = _fp8(k, -1), _fp8(v, -1)
    scale = dims.head_dim ** -0.5
    nb = t // Q_BLOCK if t % Q_BLOCK == 0 else 1
    qb = q.reshape(nb, t // nb, dims.heads, dims.head_dim)

    def block(args):
        i, qi = args
        if control:
            qi = _fp8(qi, -1)
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) * scale
        qpos = i * qi.shape[0] + jnp.arange(qi.shape[0])
        mask = jnp.arange(t)[None, :] <= qpos[:, None]
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if control:
            p = _fp8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(block, (jnp.arange(nb), qb))
    return out.reshape(t, dims.heads * dims.head_dim)


def hidden(w: dict, tokens, dims: Dims, norm: str, eps: float, theta: float,
           control: bool = False):
    """Final normed hidden states (T, D) of one sequence `tokens` (T,)."""
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    t = x.shape[0]
    hd = dims.head_dim
    layer_keys = ["wq", "wk", "wv", "wo", "gate", "up", "down"]
    if norm == "rmsnorm":
        layer_keys += ["ln1", "ln2"]
    stacked = {k: w[k] for k in layer_keys}

    def layer(x, lw):
        h = _norm(x, lw.get("ln1"), norm, eps)
        q = _mm(h, lw["wq"], control).reshape(t, dims.heads, hd)
        k = _mm(h, lw["wk"], control).reshape(t, dims.kv_heads, hd)
        v = _mm(h, lw["wv"], control).reshape(t, dims.kv_heads, hd)
        a = _attention(_rope(q, theta), _rope(k, theta), v, dims, control)
        x = x + _mm(a, lw["wo"], control)
        h = _norm(x, lw.get("ln2"), norm, eps)
        m = (jax.nn.silu(_mm(h, lw["gate"], control))
             * _mm(h, lw["up"], control))
        return x + _mm(m, lw["down"], control), None

    x, _ = jax.lax.scan(layer, x, stacked)
    return _norm(x, w.get("ln_f"), norm, eps)


@functools.partial(jax.jit, static_argnames=("dims", "norm", "eps", "theta",
                                             "n_out", "control"))
def served_gaps(w: dict, tokens, start, served, *, dims: Dims, norm: str,
                eps: float, theta: float, n_out: int, control: bool = False):
    """Gaps of the served tokens below the reference's best logit.

    `tokens` (T,) is the prompt followed by the served tokens (padded; the
    padding lies after every compared position, so causality keeps it out);
    position `start + i` predicts `served[i]` for i < n_out. Returns
    (gap of each served token, gap of the token the float8 control puts
    first there — zeros unless `control`), each (n_out,).
    """
    h = jax.lax.dynamic_slice_in_dim(
        hidden(w, tokens, dims, norm, eps, theta), start, n_out, axis=0)
    embed = w["embed"]
    logits = jnp.dot(h, embed.T.astype(jnp.float32), precision=HI)
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    gap = best - got
    if not control:
        return gap, jnp.zeros_like(gap)
    hc = jax.lax.dynamic_slice_in_dim(
        hidden(w, tokens, dims, norm, eps, theta, control=True), start,
        n_out, axis=0)
    lc = _mm(hc, embed.T, True)
    pick = jnp.argmax(lc, -1)
    ctrl = best - jnp.take_along_axis(logits, pick[:, None], axis=-1)[:, 0]
    return gap, ctrl
