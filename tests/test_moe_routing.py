"""DeepSeek-V2's routing, the held-experts MoE layer and YaRN, on the CPU at
smoke width (seeded):

- the group-limited router equals a plain NumPy transcription of the
  checkpoint's `MoEGate` (softmax scores, groups scored by their best
  expert, the top `topk_group` groups kept, top-k among their experts,
  no renormalisation, times the routed scale);
- no token is dropped when every token picks the same experts;
- a chip's share adds up: each group's experts held in turn, the parts
  sum, with the shared experts counted once, to the layer that holds all;
- YaRN's correction range, frequencies and softmax scale at the published
  numbers.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.models.attention import _mla_scale
from repro.models.common import activation, rope_tables
from repro.models.matmul import StoredWeight
from repro.models.moe import _grouped, apply_moe, moe_params, route

V2 = get_config("deepseek-v2-236b")
SMOKE = dataclasses.replace(smoke_config("deepseek-v2-236b"),
                            dtype=jnp.float32)


def hf_moe_gate(logits, n_group, topk_group, top_k, norm_topk_prob,
                routed_scaling_factor):
    """DeepseekV2 `MoEGate.forward` after the logits, in NumPy: (weights,
    expert ids), each (T, top_k)."""
    e = np.exp(logits - logits.max(-1, keepdims=True))
    scores = e / e.sum(-1, keepdims=True)
    t = scores.shape[0]
    group_scores = scores.reshape(t, n_group, -1).max(-1)
    group_idx = np.argsort(-group_scores, axis=-1)[:, :topk_group]
    group_mask = np.zeros_like(group_scores)
    np.put_along_axis(group_mask, group_idx, 1.0, axis=-1)
    score_mask = np.repeat(group_mask, scores.shape[1] // n_group, axis=-1)
    tmp_scores = np.where(score_mask > 0, scores, 0.0)
    topk_idx = np.argsort(-tmp_scores, axis=-1)[:, :top_k]
    topk_weight = np.take_along_axis(tmp_scores, topk_idx, axis=-1)
    if top_k > 1 and norm_topk_prob:
        topk_weight = topk_weight / (topk_weight.sum(-1, keepdims=True)
                                     + 1e-20)
    else:
        topk_weight = topk_weight * routed_scaling_factor
    return topk_weight, topk_idx


@pytest.mark.parametrize("cfg", [V2, SMOKE], ids=["published", "smoke"])
def test_group_limited_router_is_the_published_gate(cfg):
    rng = np.random.default_rng(11)
    logits = rng.standard_normal((256, cfg.n_experts)).astype(np.float32)
    w, idx = route(jnp.asarray(logits), cfg)
    hw, hidx = hf_moe_gate(logits.astype(np.float64), cfg.moe_n_group,
                           cfg.moe_topk_group, cfg.moe_top_k,
                           cfg.moe_norm_topk, cfg.moe_routed_scale)
    # torch.topk(sorted=False) leaves the order open: compare by expert
    order, horder = np.argsort(idx, -1), np.argsort(hidx, -1)
    np.testing.assert_array_equal(np.take_along_axis(np.asarray(idx), order,
                                                     -1),
                                  np.take_along_axis(hidx, horder, -1))
    np.testing.assert_allclose(np.take_along_axis(np.asarray(w), order, -1),
                               np.take_along_axis(hw, horder, -1),
                               rtol=1e-5)
    # every pick lies in one of the kept groups, at most topk_group of them
    per = cfg.n_experts // cfg.moe_n_group
    assert max(len(set(row // per)) for row in np.asarray(idx)) \
        <= cfg.moe_topk_group


def _experts_of(p, x, cfg, experts, weights):
    """sum_j weights[:, j] * expert experts[:, j](x), per token, plainly."""
    ex = p["experts"]
    first = cfg.held_experts[0]
    out = np.zeros(x.shape, np.float64)
    for t in range(x.shape[0]):
        for e, w in zip(experts[t], weights[t]):
            i = int(e) - first
            h = activation(cfg, x[t] @ ex["gate"][i], x[t] @ ex["up"][i])
            out[t] += float(w) * np.asarray(h @ ex["down"][i])
    return out


def _shared(p, x, cfg):
    sh = p["shared"]
    return np.asarray(activation(cfg, x @ sh["gate"], x @ sh["up"])
                      @ sh["down"])


def test_no_token_is_dropped_when_all_pick_the_same_experts():
    cfg = SMOKE
    p = moe_params(jax.random.PRNGKey(2), cfg)
    rng = np.random.default_rng(5)
    b, s, d = 2, 64, cfg.d_model
    x = jnp.asarray(np.abs(rng.standard_normal((b, s, d))), jnp.float32)
    # every token's logits favour experts 5 and 2, whatever the token
    router = np.zeros((d, cfg.n_experts), np.float32)
    router[:, 5], router[:, 2] = 3.0, 2.0
    p = dict(p, router=jnp.asarray(router))
    xt = x.reshape(b * s, d)
    w, idx = route(xt @ p["router"], cfg)
    assert (np.sort(np.asarray(idx), -1) == [2, 5]).all()
    got = np.asarray(apply_moe(p, x, cfg)).reshape(b * s, d)
    want = (_experts_of(p, np.asarray(xt), cfg, np.asarray(idx),
                        np.asarray(w)) + _shared(p, xt, cfg))
    # all 128 tokens on two experts: a capacity-bounded layer would keep
    # at most capacity_factor * T * k / E of them an expert
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_held_shares_add_up_to_the_layer_that_holds_every_expert():
    cfg = SMOKE
    whole = moe_params(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((2, 16, cfg.d_model)), jnp.float32)
    uncut = np.asarray(apply_moe(whole, x, cfg))
    per = cfg.n_experts // cfg.moe_n_group
    parts = []
    for g in range(cfg.moe_n_group):
        held = dataclasses.replace(cfg, moe_first_expert=g * per,
                                   moe_n_held=per)
        share = dict(whole, experts=jax.tree.map(
            lambda a: a[g * per:(g + 1) * per], whole["experts"]))
        parts.append(np.asarray(apply_moe(share, x, held)))
    shared = _shared(whole, x.reshape(-1, cfg.d_model), cfg).reshape(
        uncut.shape)
    total = sum(parts) - (cfg.moe_n_group - 1) * shared
    # float32 throughout; the parts differ from the whole only in the
    # order of their additions
    np.testing.assert_allclose(total, uncut, rtol=1e-5, atol=1e-5)
    # and a share holds only its own experts' part
    assert not np.allclose(parts[0], uncut, atol=1e-3)


def test_yarn_at_the_published_numbers():
    yarn, dim, theta = V2.rope_scaling, V2.rope_head_dim, V2.rope_theta
    assert yarn.correction_range(dim, theta) == (10, 23)
    assert yarn.rope_factor() == 1.0          # mscale == mscale_all_dim
    m = 0.1 * 0.707 * math.log(40) + 1
    assert yarn.softmax_factor() == pytest.approx(m * m, rel=1e-12)
    assert yarn.softmax_factor() == pytest.approx(1.590, abs=1e-3)
    assert _mla_scale(V2) == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    assert _mla_scale(V2) == pytest.approx(0.1147, abs=1e-4)
    base = theta ** (-np.arange(0, dim, 2) / dim)
    inv = yarn.inv_freq(dim, theta)
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    ramp = (16 - 10) / 13
    assert inv[16] == pytest.approx(
        base[16] / 40 * ramp + base[16] * (1 - ramp), rel=1e-6)
    pos = jnp.arange(0, 300, 7)
    cos, sin = rope_tables(pos, dim, theta, yarn)
    ang = np.asarray(pos, np.float64)[:, None] * inv
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang), atol=1e-5)
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang), atol=1e-5)
    # no scaling: the plain RoPE tables, whatever the position
    cos0, _ = rope_tables(pos, dim, theta)
    np.testing.assert_allclose(np.asarray(cos0),
                               np.cos(np.asarray(pos)[:, None] * base),
                               atol=1e-5)


@pytest.mark.parametrize("layer", [0, 1, 3])
def test_grouped_reads_its_layer_of_the_expert_stack(layer):
    """A layer's grouped GEMM over the (L·E, K, N) view of the expert stack,
    with the sizes at the layer's experts and zero at every other layer's,
    is the grouped GEMM over that layer's experts alone; rows past the last
    group read zero either way."""
    rng = np.random.default_rng(layer)
    n_layers, e, k, n = 4, 5, 16, 24
    stack = jnp.asarray(rng.standard_normal((n_layers, e, k, n)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((40, k)), jnp.float32)
    sizes = jnp.asarray([7, 0, 12, 3, 9], jnp.int32)       # 31 of 40 rows
    got = _grouped(x, StoredWeight(stack, jnp.int32(layer)), sizes)
    want = jax.lax.ragged_dot(x, stack[layer], sizes)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert not np.asarray(got[31:]).any()
