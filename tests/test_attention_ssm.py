"""Layer-level numerics: flash custom_vjp vs dense oracle, MLA absorbed form,
SSD chunked-vs-recurrent consistency, mLSTM chunkwise-vs-recurrent, decode
caches vs teacher forcing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis "
                    "(requirements-dev.txt)")
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs import smoke_config
from repro.models import ssm
from repro.models.attention import (_sdpa, chunked_sdpa, make_kv_cache,
                                    mla_attention, mla_params)
from repro.models.common import ModelConfig

RNG = np.random.default_rng(0)


def _arr(*shape):
    return jnp.asarray(RNG.standard_normal(shape), jnp.float32)


@given(s=st.sampled_from([128, 256, 384]), hkv=st.sampled_from([1, 2, 4]),
       g=st.sampled_from([1, 2]), causal=st.booleans())
@settings(max_examples=10, deadline=None)
def test_flash_equals_dense(s, hkv, g, causal):
    h = hkv * g
    q, k, v = _arr(2, s, h, 16), _arr(2, s, hkv, 16), _arr(2, s, hkv, 16)
    out_f = chunked_sdpa(q, k, v, causal=causal, chunk_q=128, chunk_k=128)
    out_d = _sdpa(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                               rtol=3e-4, atol=3e-4)


def test_flash_gradients_equal_dense():
    q, k, v = _arr(1, 256, 4, 16), _arr(1, 256, 2, 16), _arr(1, 256, 2, 16)

    def loss(f):
        return lambda *a: (f(*a) ** 2).mean()

    gc = jax.grad(loss(lambda q, k, v: chunked_sdpa(q, k, v, True, 64, 64)),
                  argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss(lambda q, k, v: _sdpa(q, k, v, True)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gc, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


def _mla_cfg():
    return smoke_config("deepseek-v2-236b")


def test_mla_absorbed_prefill_vs_decode():
    """Prefill-style MLA (no cache) must match step-by-step cached decode."""
    cfg = _mla_cfg()
    p = mla_params(jax.random.PRNGKey(0), cfg)
    x = _arr(2, 8, cfg.d_model).astype(cfg.dtype)
    full, _ = mla_attention(p, x, cfg, jnp.arange(8))

    cache = make_kv_cache(cfg, 1, 2, 8)
    outs = []
    for i in range(8):
        o, cache = mla_attention(p, x[:, i:i + 1], cfg, jnp.asarray([i]), cache)
        outs.append(o)
    stepped = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(stepped, np.float32),
                               np.asarray(full, np.float32),
                               rtol=5e-2, atol=5e-2)


def test_ssd_chunked_equals_recurrent():
    cfg = smoke_config("zamba2-1.2b")
    p = ssm.mamba2_params(jax.random.PRNGKey(1), cfg)
    x = (_arr(2, ssm.CHUNK * 2, cfg.d_model) * 0.1).astype(cfg.dtype)
    y_par, _ = ssm.mamba2_mixer(p, x, cfg, state=None)
    state = ssm.mamba2_state(cfg, 2)
    y_rec, _ = ssm.mamba2_mixer(p, x, cfg, state=state)
    np.testing.assert_allclose(np.asarray(y_par, np.float32),
                               np.asarray(y_rec, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_mlstm_chunkwise_equals_recurrent():
    cfg = smoke_config("xlstm-1.3b")
    p = ssm.mlstm_params(jax.random.PRNGKey(2), cfg)
    x = (_arr(2, ssm.CHUNK * 2, cfg.d_model) * 0.1).astype(cfg.dtype)
    y_par, _ = ssm.mlstm_mixer(p, x, cfg, state=None)
    state = ssm.mlstm_state(cfg, 2)
    y_rec, _ = ssm.mlstm_mixer(p, x, cfg, state=state)
    np.testing.assert_allclose(np.asarray(y_par, np.float32),
                               np.asarray(y_rec, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_slstm_state_continuity():
    """Running sLSTM over [a;b] == running over a then b with carried state."""
    cfg = smoke_config("xlstm-1.3b")
    p = ssm.slstm_params(jax.random.PRNGKey(3), cfg)
    x = (_arr(1, 32, cfg.d_model) * 0.1).astype(cfg.dtype)
    state = ssm.slstm_state(cfg, 1)
    y_full, _ = ssm.slstm_mixer(p, x, cfg, state=state)
    state2 = ssm.slstm_state(cfg, 1)
    y1, state2 = ssm.slstm_mixer(p, x[:, :16], cfg, state=state2)
    y2, _ = ssm.slstm_mixer(p, x[:, 16:], cfg, state=state2)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1), np.float32),
                               np.asarray(y_full, np.float32),
                               rtol=2e-3, atol=2e-3)
