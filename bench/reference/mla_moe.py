"""Plain float32 forward of DeepSeek-V2's MLA + MoE decoder, cut to one
chip's expert share, for the served-token check.

Written from the published architecture (arXiv:2405.04434 and the
checkpoint's modeling code) in `jax.numpy`, with no kernel, cache or
batching, and importing nothing of the program. Every matrix product runs
at `Precision.HIGHEST`. The weights are the canonical ones of
`bench.weights_mla_moe`, made again from the run's seed.

Architecture: token embedding; per layer an RMSNorm, then multi-head
latent attention in its naive form: q = W_uq RMSNorm(W_dq h); the KV
latent c = RMSNorm(W_dkv h) and the shared rotary key k_r = RoPE(W_kr h);
per head K = [W_uk c, k_r] and V = W_uv c; causal softmax at scale
(dn + dr)^-1/2 times YaRN's mscale^2, an output projection and a
residual. YaRN's frequencies on the dr-wide rotary part (rotate-half
form) are computed here from the configuration's `rope_scaling` numbers,
as the checkpoint's `DeepseekV2YarnRotaryEmbedding` computes them. Then an
RMSNorm and a residual SwiGLU FFN in the leading dense layers, or in the
MoE layers the routed sum plus the shared experts: float32 router logits
over all the published experts, a softmax, group-limited greedy top-k
(groups scored by their best expert, the top `topk_group` kept), weights
times the routed scale (or renormalised); of the routed sum only the
experts this chip holds are added, each computed densely on every token
and weighted by its routing weight (zero where the token did not choose
it). A final RMSNorm and the untied LM head.

`control=True` computes the same forward with every GEMM operand rounded
to float8 (e4m3, one scale per weight matrix and per activation row), the
precision below the bf16 the configuration states, which the check has to
fail.

Near-ties of the router. A token's routing can sit so close to a tie that
the rounding of the precision the configuration states decides it: two
router logits closer than the program's rounding moves them. Such a
decision is undecided by the reference, and a whole expert's output
(times the routed scale) hangs on it. `held_margin` gives, for each token
and MoE layer, how far in logit units the decisions that decide the held
experts' part of the routed sum lie from a tie: the held group's place
among the kept groups (and, while it is kept, which groups fill the other
places), and each held expert's place in the top-k. `served_gaps` does not
hold a served token to the reference where the margin at its position, at
any MoE layer, is under `tie`: its gap (and the control's) reads 0 there.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.decoder import HI, Q_BLOCK, _fp8, _mm

NEG_INF = -jnp.inf


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def yarn(dims):
    """(inv_freq (dr/2,), cos/sin factor, softmax scale) of the
    configuration's YaRN numbers."""
    factor, orig, fast, slow, mscale, mscale_all = dims.yarn
    dim, theta = dims.rope, dims.theta

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(correction_dim(fast)), 0)
    hi = min(math.ceil(correction_dim(slow)), dim - 1)
    if hi == lo:
        hi += 0.001
    ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
    extra = 1.0 / theta ** (np.arange(0, dim, 2) / dim)
    inter = extra / factor
    inv_freq = inter * ramp + extra * (1.0 - ramp)
    table = _yarn_mscale(factor, mscale) / _yarn_mscale(factor, mscale_all)
    scale = (dims.nope + dims.rope) ** -0.5
    if mscale_all:
        scale *= _yarn_mscale(factor, mscale_all) ** 2
    return inv_freq.astype(np.float32), table, scale


def _rope(x, inv_freq, table):
    """x (T, heads, dr): rotate-half RoPE at positions 0..T-1."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    c = (jnp.cos(ang) * table)[:, None, :]
    s = (jnp.sin(ang) * table)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _attention(q, k, v, scale, control):
    """Causal multi-head attention in query blocks. q, k (T, H, dk);
    v (T, H, dv) -> (T, H * dv)."""
    t, h, _ = q.shape
    if control:
        k, v = _fp8(k, -1), _fp8(v, -1)
    nb = t // Q_BLOCK if t % Q_BLOCK == 0 else 1
    qb = q.reshape(nb, t // nb, h, q.shape[-1])

    def block(args):
        i, qi = args
        if control:
            qi = _fp8(qi, -1)
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=HI) * scale
        qpos = i * qi.shape[0] + jnp.arange(qi.shape[0])
        mask = jnp.arange(t)[None, :] <= qpos[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], s, NEG_INF), axis=-1)
        if control:
            p = _fp8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(block, (jnp.arange(nb), qb))
    return out.reshape(t, h * v.shape[-1])


def _mla(x, lw, dims, rope, control):
    """x + MLA(RMSNorm(x)) of one layer, naive form."""
    inv_freq, table, scale = rope
    t, h = x.shape[0], dims.heads
    dn, dr = dims.nope, dims.rope
    a = _rms(x, lw["ln1"], dims.eps)
    cq = _rms(_mm(a, lw["w_dq"], control), lw["q_norm"], dims.eps)
    q = _mm(cq, lw["w_uq"], control).reshape(t, h, dn + dr)
    c = _rms(_mm(a, lw["w_dkv"], control), lw["kv_norm"], dims.eps)
    k_r = _rope(_mm(a, lw["w_kr"], control)[:, None, :], inv_freq, table)
    k_nope = _mm(c, lw["w_uk"], control).reshape(t, h, dn)
    v = _mm(c, lw["w_uv"], control).reshape(t, h, dims.v_head)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], inv_freq, table)],
                        -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_r, (t, h, dr))], -1)
    return x + _mm(_attention(q, k, v, scale, control), lw["wo"], control)


def _swiglu(h, gate, up, down, control):
    m = jax.nn.silu(_mm(h, gate, control)) * _mm(h, up, control)
    return _mm(m, down, control)


def routing(logits, dims):
    """(T, E) float32 logits -> top-k weights and expert ids, (T, k)."""
    scores = jax.nn.softmax(logits, axis=-1)
    t, e = scores.shape
    if dims.n_group > 1:
        per = e // dims.n_group
        best = scores.reshape(t, dims.n_group, per).max(-1)
        _, groups = jax.lax.top_k(best, dims.topk_group)
        keep = jnp.zeros((t, dims.n_group), bool).at[
            jnp.arange(t)[:, None], groups].set(True)
        scores = jnp.where(jnp.repeat(keep, per, axis=1), scores, 0.0)
    w, idx = jax.lax.top_k(scores, dims.top_k)
    if dims.norm_topk:
        return w / (w.sum(-1, keepdims=True) + 1e-20), idx
    return w * dims.routed_scale, idx


def held_margin(logits, dims):
    """(T, E) float32 logits -> (T,): the smallest change of the difference
    of two logits that lets a held expert into or out of the token's top k,
    through its group's rank or its own (inf where none can). The groups:
    a held group out of the kept ones is that far from the last kept
    group; while one is kept, the last kept group and the first left out
    may trade places (the experts competing with the held ones change). The
    experts, in a kept held group: each held expert's distance from the
    first left out (if chosen) or the last chosen (if not)."""
    t, e = logits.shape
    ids = jnp.arange(e)
    held = (ids >= dims.first_held) & (ids < dims.first_held + dims.held)
    pool, group = logits, jnp.full((t,), jnp.inf)
    if dims.n_group > 1:
        per = e // dims.n_group
        best = logits.reshape(t, dims.n_group, per).max(-1)
        top = jax.lax.top_k(best, dims.topk_group + 1)[0]
        last, out = top[:, -2], top[:, -1]
        held_group = held.reshape(dims.n_group, per).any(-1)
        best_held = jnp.where(held_group, best, NEG_INF).max(-1)
        group = jnp.maximum(last - out, last - best_held)
        kept = jnp.repeat(best >= last[:, None], per, axis=1)
        pool = jnp.where(kept, logits, NEG_INF)
    top = jax.lax.top_k(pool, dims.top_k + 1)[0]
    chosen, first_out = top[:, -2:-1], top[:, -1:]
    dist = jnp.where(pool >= chosen, pool - first_out, chosen - pool)
    expert = jnp.where(held & (pool > NEG_INF), dist, jnp.inf).min(-1)
    return jnp.minimum(group, expert)


def _experts(h, lw, dims, control):
    """The held experts' part of the routed sum: every held expert on every
    token, weighted by the token's routing weight for it; and the token's
    `held_margin`."""
    logits = _mm(h, lw["router"], control)
    w, idx = routing(logits, dims)
    held = dims.first_held + jnp.arange(dims.held)
    gate_w = jnp.einsum("tk,tke->te", w,
                        (idx[:, :, None] == held).astype(jnp.float32))
    wg, wu, wd = (lw[n].astype(jnp.float32) for n in ("gate", "up", "down"))
    if control:
        h = _fp8(h, -1)
        wg, wu, wd = (_fp8(m, (1, 2)) for m in (wg, wu, wd))
    g = jnp.einsum("td,edf->etf", h, wg, precision=HI)
    u = jnp.einsum("td,edf->etf", h, wu, precision=HI)
    m = jax.nn.silu(g) * u
    if control:
        m = _fp8(m, -1)
    y = jnp.einsum("etf,efd->etd", m, wd, precision=HI)
    return (jnp.einsum("te,etd->td", gate_w, y, precision=HI),
            held_margin(logits, dims))


def forward(w: dict, tokens, dims, control: bool = False):
    """Final normed hidden states (T, D) of one sequence `tokens` (T,), and
    the smallest `held_margin` of each position over the MoE layers (T,)."""
    x = jnp.take(w["embed"], tokens, axis=0).astype(jnp.float32)
    rope = yarn(dims)

    def group(name):
        p = name + "."
        return {k[len(p):]: v for k, v in w.items() if k.startswith(p)}

    def dense(x, lw):
        x = _mla(x, lw, dims, rope, control)
        h = _rms(x, lw["ln2"], dims.eps)
        return x + _swiglu(h, lw["gate"], lw["up"], lw["down"], control), None

    def moe(x, lw):
        x = _mla(x, lw, dims, rope, control)
        h = _rms(x, lw["ln2"], dims.eps)
        out, margin = _experts(h, lw, dims, control)
        out = out + _swiglu(h, lw["shared_gate"], lw["shared_up"],
                            lw["shared_down"], control)
        return x + out, margin

    x, _ = jax.lax.scan(dense, x, group("dense"))
    x, margins = jax.lax.scan(moe, x, group("moe"))
    return _rms(x, w["ln_f"], dims.eps), margins.min(0)


def hidden(w: dict, tokens, dims, control: bool = False):
    """Final normed hidden states (T, D) of one sequence `tokens` (T,)."""
    return forward(w, tokens, dims, control)[0]


@functools.partial(jax.jit, static_argnames=("dims", "n_out", "control",
                                             "tie"))
def served_gaps(w: dict, tokens, start, served, *, dims, n_out: int,
                control: bool = False, tie: float = 0.0):
    """Gaps of the served tokens below the reference's best logit.

    `tokens` (T,) is the prompt followed by the served tokens (padded; the
    padding lies after every compared position, so causality keeps it out);
    position `start + i` predicts `served[i]` for i < n_out. Returns
    (gap of each served token, gap of the token the float8 control puts
    first there — zeros unless `control`), each (n_out,), both 0 where the
    reference's routing at the position is within `tie` of a tie.
    """
    h, margin = forward(w, tokens, dims)
    h = jax.lax.dynamic_slice_in_dim(h, start, n_out, axis=0)
    checked = jax.lax.dynamic_slice_in_dim(margin, start, n_out) >= tie
    head = w["lm_head"]
    logits = jnp.dot(h, head.astype(jnp.float32), precision=HI)
    best = logits.max(-1)
    got = jnp.take_along_axis(logits, served[:, None], axis=-1)[:, 0]
    gap = jnp.where(checked, best - got, 0.0)
    if not control:
        return gap, jnp.zeros_like(gap)
    hc = jax.lax.dynamic_slice_in_dim(hidden(w, tokens, dims, control=True),
                                      start, n_out, axis=0)
    pick = jnp.argmax(_mm(hc, head, True), -1)
    ctrl = best - jnp.take_along_axis(logits, pick[:, None], axis=-1)[:, 0]
    return gap, jnp.where(checked, ctrl, 0.0)
