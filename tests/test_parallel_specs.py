"""Sharding rule table + fused-loss numerics."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import smoke_config
from repro.launch.mesh import make_mesh
from repro.models.matmul import weight_value
from repro.models.model import init_params, lm_head_weight
from repro.train.steps import chunked_xent


def _mesh_stub():
    # spec fitting only needs axis sizes; use the real device for a 1x1 mesh
    return make_mesh((1, 1), ("data", "model"))


def test_param_spec_fitting_drops_indivisible_axes():
    from repro.parallel.spec_rules import _fit
    mesh = _mesh_stub()

    class M:
        shape = {"data": 16, "model": 16}
    spec = _fit(P("data", "model"), (64, 160), M)
    # 64 % 16 == 0 keeps 'data'; 160 % 16 == 0 keeps 'model'
    assert spec == P("data", "model")
    spec = _fit(P("data", "model"), (60, 160), M)
    assert spec == P(None, "model")
    spec = _fit(P(("pod", "data"), None), (8, 4), type("M2", (), {
        "shape": {"pod": 2, "data": 16}}))
    assert spec == P(None, None)     # 8 % 32 != 0


class _KeyEntry:
    def __init__(self, k):
        self.key = k


class _Mesh16x16:
    axis_names = ("data", "model")
    shape = {"data": 16, "model": 16}


@pytest.mark.parametrize("n_kv,axis", [(8, 3), (32, 2)],
                         ids=["seq_fallback", "heads"])
def test_cache_spec_prefers_heads_then_seq(n_kv, axis):
    """K/V are (L, B, Hkv, S, D): KV heads over 'model' when they divide
    it, else the sequence (qwen3: kv=8 < 16)."""
    from repro.parallel.spec_rules import cache_spec
    cfg = smoke_config("qwen3-14b")
    leaf = jax.ShapeDtypeStruct((40, 128, n_kv, 32768, 128), jnp.bfloat16)
    spec = cache_spec((_KeyEntry("layers"), _KeyEntry("k")), leaf,
                      _Mesh16x16, cfg, 128)
    assert [i for i, a in enumerate(spec) if a == "model"] == [axis]


def test_cache_spec_follows_decode_init_layout():
    """The rule reads the axes `decode_init` lays the cache out in: the
    'model' axis of K and V lands on the KV-head dim."""
    from repro.models.model import decode_init
    from repro.parallel.spec_rules import cache_spec
    cfg = dataclasses.replace(smoke_config("qwen3-14b"), n_kv_heads=16,
                              n_heads=32)
    caches = jax.eval_shape(lambda: decode_init(None, cfg, 16, 64))
    for name in ("k", "v"):
        leaf = caches["layers"][name]
        spec = cache_spec((_KeyEntry("layers"), _KeyEntry(name)), leaf,
                          _Mesh16x16, cfg, 16)
        assert spec[2] == "model" and leaf.shape[2] == cfg.n_kv_heads


def test_chunked_xent_matches_direct():
    cfg = smoke_config("olmo-1b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    hidden = jnp.asarray(rng.standard_normal((2, 24, cfg.d_model)) * 0.1,
                         cfg.dtype)
    targets = jnp.asarray(rng.integers(0, cfg.vocab, (2, 24)), jnp.int32)
    w = weight_value(lm_head_weight(params, cfg))
    fused = chunked_xent(hidden, w, targets, cfg.vocab, chunk=8)
    logits = (hidden @ w).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, -1)
    direct = -jnp.take_along_axis(logp, targets[..., None], -1).mean()
    np.testing.assert_allclose(float(fused), float(direct), rtol=1e-5)


def test_chunked_xent_gradients_flow():
    cfg = smoke_config("olmo-1b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    hidden = jnp.asarray(rng.standard_normal((2, 16, cfg.d_model)) * 0.1,
                         jnp.float32)
    targets = jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32)
    w = weight_value(lm_head_weight(params, cfg)).astype(jnp.float32)
    g = jax.grad(lambda h: chunked_xent(h, w, targets, cfg.vocab, chunk=8))(hidden)
    assert np.isfinite(np.asarray(g)).all()
    assert float(jnp.abs(g).max()) > 0


def test_hlo_collective_parser():
    from repro.launch.hlo_stats import collective_stats
    hlo = """
HloModule m
ENTRY %main (p: f32[64,64]) -> f32[64,64] {
  %p = f32[64,64] parameter(0)
  %ag = f32[128,64] all-gather(%p), replica_groups={{0,1}}, dimensions={0}
  %ar = f32[128,64] all-reduce(%ag), to_apply=%add
  ROOT %out = f32[64,64] slice(%ar), slice={[0:64], [0:64]}
}
"""
    stats = collective_stats(hlo)
    assert stats.count_by_kind["all-gather"] == 1
    assert stats.count_by_kind["all-reduce"] == 1
    assert stats.bytes_by_kind["all-gather"] == 128 * 64 * 4
