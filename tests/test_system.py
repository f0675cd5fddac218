"""End-to-end system tests: every smoke arch trains (loss decreases, no
NaNs), decodes, checkpoints and recovers from injected failures; the
launchers' start-up settings (plan grid, compile cache) resolve as
documented."""
import dataclasses
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import list_archs, smoke_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.ft.runtime import FailureInjector, LoopConfig, run_training
from repro.models.model import decode_init, decode_step, forward, init_params
from repro.optim import adamw, compress
from repro.train.steps import make_serve_step, make_train_step

KEY = jax.random.PRNGKey(0)


def _batch_for(cfg, b, s, rng):
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32),
        "targets": jnp.asarray(rng.integers(0, cfg.vocab, (b, s)), jnp.int32),
    }
    if cfg.frontend == "vision_stub":
        batch["prefix_embeds"] = jnp.ones((b, cfg.n_prefix, cfg.d_model),
                                          jnp.bfloat16) * 0.01
    if cfg.is_encoder_decoder:
        batch["encoder_embeds"] = jnp.ones((b, cfg.n_prefix, cfg.d_model),
                                           jnp.bfloat16) * 0.01
    return batch


@pytest.mark.parametrize("arch", list_archs())
def test_forward_shapes_and_finite(arch):
    cfg = smoke_config(arch)
    params = init_params(KEY, cfg)
    rng = np.random.default_rng(0)
    batch = _batch_for(cfg, 2, 32, rng)
    kwargs = {}
    if "prefix_embeds" in batch:
        kwargs["prefix_embeds"] = batch["prefix_embeds"]
    if "encoder_embeds" in batch:
        kwargs["encoder_embeds"] = batch["encoder_embeds"]
    logits = forward(params, batch["tokens"], cfg, remat=False, **kwargs)
    assert logits.shape[0] == 2 and logits.shape[-1] == cfg.vocab
    assert np.isfinite(np.asarray(logits)).all()


# zamba2's scanned hybrid super-layers are the one smoke train step that
# breaks the 10s budget — it rides the full lane (CI's fast lane runs the
# other nine archs, which cover every other block kind)
@pytest.mark.parametrize("arch", [
    pytest.param(a, marks=pytest.mark.slow if a == "zamba2-1.2b" else [])
    for a in list_archs()])
def test_train_step_runs(arch):
    cfg = smoke_config(arch)
    params = init_params(KEY, cfg)
    opt = adamw.AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=10)
    ostate = adamw.init(params)
    step = jax.jit(make_train_step(cfg, opt))
    rng = np.random.default_rng(1)
    batch = _batch_for(cfg, 2, 32, rng)
    params2, ostate2, _, metrics = step(params, ostate, None, batch)
    assert np.isfinite(float(metrics["loss"]))
    # params actually changed
    delta = jax.tree.map(lambda a, b: float(jnp.abs(a.astype(jnp.float32)
                                                    - b.astype(jnp.float32)).max()),
                         params, params2)
    assert max(jax.tree.leaves(delta)) > 0


@pytest.mark.parametrize("arch", ["olmo-1b", "deepseek-moe-16b", "zamba2-1.2b",
                                  "xlstm-1.3b", "seamless-m4t-medium"])
def test_decode_steps(arch):
    cfg = smoke_config(arch)
    params = init_params(KEY, cfg)
    serve = jax.jit(make_serve_step(cfg))
    caches = decode_init(params, cfg, 2, 16)
    tok = jnp.zeros((2, 1), jnp.int32)
    kwargs = {}
    if cfg.is_encoder_decoder:
        enc = jnp.ones((2, cfg.n_prefix, cfg.d_model), cfg.dtype) * 0.01
        kwargs = {"encoder_out": enc @ params["frontend_proj"]}
    for i in range(3):
        logits, caches = serve(params, caches, tok, jnp.asarray(i), **kwargs)
        tok = jnp.argmax(logits, -1)[:, None] % cfg.vocab
    assert np.isfinite(np.asarray(logits)).all()


def test_loss_decreases_with_failure_recovery(tmp_path):
    cfg = smoke_config("olmo-1b")
    opt = adamw.AdamWConfig(lr=1e-3, warmup_steps=5, total_steps=40)
    params = init_params(KEY, cfg)
    state0 = (params, adamw.init(params), compress.init(params))
    raw = jax.jit(make_train_step(cfg, opt, microbatches=2, compress_grads=True))

    def step_fn(state, batch):
        p, o, c = state
        p, o, c, m = raw(p, o, c, batch)
        return (p, o, c), m

    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=8))
    losses = []
    run_training(
        step_fn, state0, data,
        LoopConfig(total_steps=40, ckpt_every=10, ckpt_dir=str(tmp_path)),
        make_batch_arrays=lambda b: {k: jnp.asarray(v) for k, v in b.items()},
        injector=FailureInjector(fail_at={15}),
        on_metrics=lambda s, m: losses.append((s, float(m["loss"]))))
    first = np.mean([l for s, l in losses if s < 5])
    last = np.mean([l for s, l in losses if s >= 35])
    assert last < first - 0.2, f"no learning: {first} -> {last}"
    # failure at 15 was recovered: steps continued past it
    assert max(s for s, _ in losses) == 39


# float32 shows a layer mix-up at any size; bfloat16 is what serving runs.
# MLA's absorbed decode form and the naive forward round apart by up to
# 0.07 in bfloat16 (tests/test_attention_ssm.py bounds the two forms).
_DECODE_EQUIVALENCE = (
    [(a, jnp.bfloat16, 2e-2)
     for a in ("olmo-1b", "phi4-mini-3.8b", "deepseek-moe-16b")]
    + [(a, jnp.float32, 1e-4) for a in ("olmo-1b", "phi4-mini-3.8b",
                                        "deepseek-v2-236b",
                                        "deepseek-moe-16b")])


@pytest.mark.parametrize("arch,dtype,tol", _DECODE_EQUIVALENCE,
                         ids=lambda v: getattr(v, "__name__", str(v)))
def test_decode_matches_forward_logits(arch, dtype, tol):
    """Chunked prefill, then one token a step, must agree with
    teacher-forced forward at every position a step returns: MHA, GQA,
    MLA and the MoE groups, each layer with weights of its own, so a step
    that read or wrote another layer's cache would miss. The MoE layer
    drops no token, so forward over 16 tokens and a step over 4 or 1 route
    each token alike (the capacity-bounded layer this replaced needed its
    buffers raised here to drop none)."""
    cfg = dataclasses.replace(smoke_config(arch), dtype=dtype)
    if cfg.n_experts:
        # half the experts held: the steps and forward compute the same
        # share, and a token routed elsewhere must add nothing in either
        cfg = dataclasses.replace(cfg, moe_n_held=cfg.n_experts // 2)
    params = init_params(KEY, cfg)
    rng = np.random.default_rng(3)
    b, prompt, chunk, total = 2, 12, 4, 16
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (b, total)), jnp.int32)
    full_logits = forward(params, tokens, cfg, remat=False)
    step = jax.jit(make_serve_step(cfg))
    caches = decode_init(params, cfg, b, total + 4)
    got, at = [], []
    for start, n in ([(c, chunk) for c in range(0, prompt, chunk)]
                     + [(t, 1) for t in range(prompt, total)]):
        logits, caches = step(params, caches, tokens[:, start:start + n],
                              jnp.arange(start, start + n))
        got.append(logits)
        at.append(start + n - 1)
    np.testing.assert_allclose(np.stack(got, 1),
                               np.asarray(full_logits[:, at]),
                               rtol=tol, atol=tol)


def test_plan_grid_defaults_to_launcher_mesh():
    """Plans are priced for the mesh the launcher builds unless
    `--plan-grid` names another pod view."""
    import argparse

    from repro.deploy.warmup import add_plan_args, resolve_plan_grid
    ap = argparse.ArgumentParser()
    add_plan_args(ap)
    assert resolve_plan_grid(ap.parse_args([]), (1, 1)) == (1, 1)
    assert resolve_plan_grid(ap.parse_args(["--plan-grid", "4", "4"]),
                             (1, 1)) == (4, 4)


def test_compile_cache_dir(monkeypatch, tmp_path):
    """`JAX_COMPILATION_CACHE_DIR`, when set, is the cache directory; unset,
    the cache sits at one fixed path inside the checkout."""
    from pathlib import Path

    from repro.launch import compile_cache
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {key: getattr(jax.config, key) for key in keys}
    try:
        monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        monkeypatch.delenv(compile_cache.ENV)
        checkout = Path(__file__).resolve().parents[1]
        assert compile_cache.enable_compile_cache() == str(
            checkout / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(
            checkout / ".jax_cache")
    finally:
        for key, val in was.items():
            jax.config.update(key, val)
