"""Plan-routed model matmuls (models/matmul.pmm + shard_ctx.GemmContext).

Covers the PR-2 contracts:
- with no gemm context, pmm is exactly `x @ w` and every block kind's
  forward is bit-for-bit unchanged (recording must not perturb numerics);
- the tied-embedding logits refactor (einsum -> x @ embed.T) is exact;
- dit_gemm derives the planner GEMMShape from flattened leading dims
  (regression: batched operands used to read a.shape[0]/b.shape[1] raw);
- model_workload is cross-validated against the (tag, GEMMShape) pairs the
  model actually traces — exact coverage for gqa/MLA/MoE/mamba2/xlstm/vlm
  and the encoder-decoder stack (seamless, incl. cross-attention K/V);
- a serve-style installed context routes matmuls through dit_gemm with
  plan hits for the model's workload shapes (multidevice, subprocess).
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.schedule import GEMMShape
from repro.deploy import (Planner, model_workload, moe_dispatch_geometry,
                          workload_coverage)
from repro.hw.config import AcceleratorConfig, HBMConfig, NoCConfig, TileConfig
from repro.launch.mesh import make_mesh
from repro.models import shard_ctx
from repro.models.matmul import pmm
from repro.models.model import forward, init_params
from repro.models.shard_ctx import GemmContext

MINI = AcceleratorConfig(name="mini", grid=(4, 4),
                         tile=TileConfig(l1_bytes=4 * 1024 * 1024),
                         noc=NoCConfig(), hbm=HBMConfig(n_channels=8))

# one smoke arch per block kind the satellite names (vlm joined when
# model_workload learned the modality-frontend projection; encdec joined
# when it learned the encoder blocks + cross-attention K/V projections)
BLOCK_KINDS = {
    "gqa": "gemma-2b",
    "mla": "deepseek-v2-236b",
    "moe": "deepseek-moe-16b",
    "mamba2": "zamba2-1.2b",
    "xlstm": "xlstm-1.3b",
    "vlm": "phi-3-vision-4.2b",
    "encdec": "seamless-m4t-medium",
}


def _stub_embeds(cfg, batch: int, key: str, abstract: bool):
    shape = (batch, cfg.n_prefix, cfg.d_model)
    if abstract:
        return {key: jax.ShapeDtypeStruct(shape, jnp.bfloat16)}
    rng = np.random.default_rng(9)
    return {key: jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)}


def _prefill_kwargs(cfg, batch: int, abstract: bool = True):
    """Extra forward() inputs a modality-frontend arch needs (the VLM stub's
    precomputed patch embeddings / the enc-dec stub's frame embeddings)."""
    if getattr(cfg, "frontend", "none") == "vision_stub":
        return _stub_embeds(cfg, batch, "prefix_embeds", abstract)
    if getattr(cfg, "is_encoder_decoder", False):
        return _stub_embeds(cfg, batch, "encoder_embeds", abstract)
    return {}


def _decode_kwargs(cfg, batch: int):
    """Extra decode_step() inputs: enc-dec archs attend to the precomputed
    encoder output every step (cross-attention K/V re-project it)."""
    if getattr(cfg, "is_encoder_decoder", False):
        return _stub_embeds(cfg, batch, "encoder_out", abstract=True)
    return {}


# ---------------------------------------------------------------------------
# pmm fallback contract
# ---------------------------------------------------------------------------

def test_pmm_no_context_is_plain_matmul():
    rng = np.random.default_rng(0)
    for shape, dtype in (((6, 16), jnp.float32), ((2, 5, 16), jnp.bfloat16),
                         ((2, 3, 4, 16), jnp.bfloat16)):
        x = jnp.asarray(rng.standard_normal(shape), dtype)
        w = jnp.asarray(rng.standard_normal((16, 8)), dtype)
        assert shard_ctx.get_gemm_context() is None
        assert jnp.array_equal(pmm(x, w, tag="t"), x @ w)


def test_pmm_record_only_context_is_bitwise_transparent():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 5, 16)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((16, 8)), jnp.bfloat16)
    base = x @ w
    ctx = GemmContext(mesh=None)
    with shard_ctx.gemm_context(ctx):
        out = pmm(x, w, tag="probe")
    assert jnp.array_equal(out, base)
    assert ctx.stats.unrouted == 1
    assert ("probe", GEMMShape(10, 8, 16)) in ctx.stats.observed


def test_tied_head_matmul_matches_prerefactor_einsum():
    """The lm-head refactor: einsum('bsd,vd->bsv') became x @ embed.T."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2, 7, 32)), jnp.bfloat16)
    embed = jnp.asarray(rng.standard_normal((64, 32)), jnp.bfloat16)
    old = jnp.einsum("bsd,vd->bsv", x, embed)
    new = pmm(x, embed.T, tag="lm_head")
    assert jnp.array_equal(old, new)


# ---------------------------------------------------------------------------
# per-block-kind forward parity (no mesh)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", sorted(BLOCK_KINDS))
def test_forward_parity_no_mesh(kind):
    """pmm-routed forward == the x @ w baseline bit-for-bit with no mesh:
    the no-context path and the record-only path must agree exactly (the
    fallback is literally `x @ w`, and recording is trace-time only)."""
    cfg = smoke_config(BLOCK_KINDS[kind])
    rng = np.random.default_rng(3)
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32)
    kwargs = _prefill_kwargs(cfg, 2, abstract=False)
    base = forward(params, toks, cfg, **kwargs)
    ctx = GemmContext(mesh=None)
    with shard_ctx.gemm_context(ctx):
        recorded = forward(params, toks, cfg, **kwargs)
    assert jnp.array_equal(base, recorded)
    assert ctx.stats.observed, "forward traced no pmm calls"


# ---------------------------------------------------------------------------
# dit_gemm batched-operand regression
# ---------------------------------------------------------------------------

def test_dit_gemm_batched_planner_shape_regression():
    """The planner path used to build GEMMShape(a.shape[0], b.shape[1],
    a.shape[1]) — wrong (and shard_map-fatal) for batched operands. Leading
    dims must flatten into M for both the lookup and the dispatch."""
    from repro.core.gemm import dit_gemm
    mesh = make_mesh((1, 1), ("data", "model"))
    planner = Planner(MINI, elem_bytes=4, max_candidates=8)
    rng = np.random.default_rng(4)
    a = jnp.asarray(rng.standard_normal((2, 8, 32)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    out = dit_gemm(a, b, mesh, planner=planner)
    assert out.shape == (2, 8, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a) @ np.asarray(b),
                               rtol=1e-5, atol=1e-5)
    # the planner was consulted with the flattened problem, not (2, 16, 8)
    assert planner.cache.contains(GEMMShape(16, 16, 32), 4, MINI)
    assert not planner.cache.contains(GEMMShape(2, 16, 8), 4, MINI)


def test_dit_gemm_batched_plan_dispatch():
    """A tuned plan dispatches batched operands through its dataflow."""
    from repro.core.gemm import dit_gemm
    from repro.core.schedule import Schedule, Tiling
    mesh = make_mesh((1, 1), ("data", "model"))
    rng = np.random.default_rng(5)
    a = jnp.asarray(rng.standard_normal((4, 8, 32)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    sched = Schedule(GEMMShape(32, 16, 32), Tiling(4, 4, 1, tk=8), "summa")
    out = jax.jit(lambda x, y: dit_gemm(x, y, mesh, plan=sched))(a, b)
    assert out.shape == (4, 8, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a) @ np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_dit_gemm_modes_differentiable():
    """Routed training backprops through the collective loops: every mode's
    scan-based panel/skew/rotate loop must have a reverse-mode path."""
    from repro.core.gemm import dit_gemm
    mesh = make_mesh((1, 1), ("data", "model"))
    rng = np.random.default_rng(6)
    a = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    ones = jnp.ones((8, 8), jnp.float32)
    for mode in ("auto", "summa", "cannon", "splitk", "allgather"):
        ga, gb = jax.grad(
            lambda x, y, m=mode: dit_gemm(x, y, mesh, mode=m).sum(),
            argnums=(0, 1))(a, b)
        np.testing.assert_allclose(np.asarray(ga), np.asarray(ones @ b.T),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gb), np.asarray(a.T @ ones),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# model_workload cross-validation against the recorded workload
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", sorted(BLOCK_KINDS))
def test_model_workload_cross_validation(kind):
    """model_workload must describe exactly the GEMMs the model runs: every
    predicted shape is observed and every observed shape predicted — at
    100% coverage for every block kind, including the encoder-decoder
    stack (encoder blocks + decoder cross-attention K/V projections)."""
    cfg = smoke_config(BLOCK_KINDS[kind])
    b, s = 2, 16
    kwargs = _prefill_kwargs(cfg, b)
    ctx = GemmContext(mesh=None)
    with shard_ctx.gemm_context(ctx):
        pshapes = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg))
        toks = jax.ShapeDtypeStruct((b, s), jnp.int32)
        jax.eval_shape(lambda p, t, **kws: forward(p, t, cfg, **kws),
                       pshapes, toks, **kwargs)
    observed = ctx.stats.observed_shapes()
    predicted = model_workload(cfg, b, s, kind="prefill")
    cov = workload_coverage(predicted, observed)
    assert cov["covered"] == 1.0, f"unpredicted shapes: {cov['extra']}"
    assert cov["missing"] == [], f"never-executed shapes: {cov['missing']}"


@pytest.mark.parametrize("kind", sorted(BLOCK_KINDS))
def test_model_workload_cross_validation_decode(kind):
    """Decode kind must match the decode path — including MLA's absorbed
    form (q-absorb / v-un-absorb contractions instead of K/V up-projection)
    and the recurrent SSM/xLSTM mixers."""
    from repro.models.model import decode_init, decode_step
    cfg = smoke_config(BLOCK_KINDS[kind])
    b, max_len = 2, 16
    kwargs = _decode_kwargs(cfg, b)
    ctx = GemmContext(mesh=None)
    with shard_ctx.gemm_context(ctx):
        pshapes = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg))
        caches = jax.eval_shape(
            lambda: decode_init({}, cfg, b, max_len))
        toks = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        pos = jax.ShapeDtypeStruct((), jnp.int32)
        jax.eval_shape(lambda p, c, t, i, **kws: decode_step(
                           p, c, t, i, cfg, encoder_out=kws.get("encoder_out")),
                       pshapes, caches, toks, pos, **kwargs)
    observed = ctx.stats.observed_shapes()
    predicted = model_workload(cfg, b, max_len, kind="decode")
    cov = workload_coverage(predicted, observed)
    assert cov["covered"] == 1.0, f"unpredicted shapes: {cov['extra']}"
    assert cov["missing"] == [], f"never-executed shapes: {cov['missing']}"


@pytest.mark.parametrize("meshed", [False, True],
                         ids=["held_experts", "expert_sharded_mesh"])
def test_moe_geometry_prediction_matches_model(meshed):
    """The expert GEMM shapes model_workload (deploy, jax-free) predicts
    are the ones moe.apply_moe records: with no activation mesh, the held
    experts' grouped GEMMs at the static budget of tokens * k rows; under
    one, the capacity-bounded dispatch buffers (moe_dispatch_geometry)."""
    cfg = smoke_config("deepseek-moe-16b")
    b, s = 2, 16
    rows = (moe_dispatch_geometry(b * s, cfg)[1] if meshed
            else b * s * cfg.moe_top_k)
    predicted = set(model_workload(cfg, b, s, dp=1 if meshed else None))
    ctx = GemmContext(mesh=None)
    mesh = make_mesh((1, 1), ("data", "model")) if meshed else None
    shard_ctx.set_mesh(mesh)
    try:
        with shard_ctx.gemm_context(ctx):
            pshapes = jax.eval_shape(
                lambda: init_params(jax.random.PRNGKey(0), cfg))
            toks = jax.ShapeDtypeStruct((b, s), jnp.int32)
            jax.eval_shape(lambda p, t: forward(p, t, cfg), pshapes, toks)
    finally:
        shard_ctx.set_mesh(None)
    expert_shapes = {shape for (tag, shape) in ctx.stats.observed
                     if tag.startswith("moe.expert")}
    assert expert_shapes == {GEMMShape(rows, cfg.moe_d_ff, cfg.d_model),
                             GEMMShape(rows, cfg.d_model, cfg.moe_d_ff)}
    assert expert_shapes <= predicted


# ---------------------------------------------------------------------------
# routed dispatch: single-device end to end, multidevice in a subprocess
# ---------------------------------------------------------------------------

def test_routed_forward_matches_baseline_single_device():
    """Warm planner + live mesh: forward routes through dit_gemm with a
    100% resolve rate and matches the unrouted numerics."""
    cfg = smoke_config("gemma-2b")
    rng = np.random.default_rng(7)
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (2, 16)), jnp.int32)
    base = forward(params, toks, cfg)

    planner = Planner(MINI, elem_bytes=4, max_candidates=8)
    planner.batch_tune(model_workload(cfg, 2, 16, kind="prefill"))
    mesh = make_mesh((1, 1), ("data", "model"))
    ctx = GemmContext(mesh=mesh, planner=planner)
    with shard_ctx.gemm_context(ctx):
        routed = jax.jit(lambda p, t: forward(p, t, cfg))(params, toks)
    assert ctx.stats.routed > 0 and ctx.stats.fallback == 0
    assert ctx.stats.resolve_rate == 1.0
    np.testing.assert_allclose(np.asarray(routed, np.float32),
                               np.asarray(base, np.float32),
                               rtol=2e-2, atol=2e-2)


SRC = os.path.join(os.path.dirname(__file__), "..", "src")

MULTIDEVICE_BODY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import smoke_config
    from repro.deploy import Planner, model_workload
    from repro.hw.config import (AcceleratorConfig, HBMConfig, NoCConfig,
                                 TileConfig)
    from repro.launch.mesh import make_mesh
    from repro.models import shard_ctx
    from repro.models.model import forward, init_params
    from repro.models.shard_ctx import GemmContext

    MINI = AcceleratorConfig(name="mini", grid=(4, 4),
                             tile=TileConfig(l1_bytes=4 * 1024 * 1024),
                             noc=NoCConfig(), hbm=HBMConfig(n_channels=8))
    cfg = smoke_config("gemma-2b")
    rng = np.random.default_rng(0)
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32)
    base = np.asarray(forward(params, toks, cfg), np.float32)

    # serve-style: warm the planner for the model workload, install the
    # context, trace on a 2x2 mesh
    planner = Planner(MINI, elem_bytes=4, max_candidates=8)
    planner.batch_tune(model_workload(cfg, 4, 16, kind="prefill"))
    mesh = make_mesh((2, 2), ("data", "model"))
    ctx = GemmContext(mesh=mesh, planner=planner)
    shard_ctx.set_gemm_context(ctx)
    routed = np.asarray(
        jax.jit(lambda p, t: forward(p, t, cfg))(params, toks), np.float32)
    shard_ctx.set_gemm_context(None)

    s = ctx.stats
    assert s.routed > 0, "nothing routed"
    assert s.fallback == 0, f"plan misses: {s.describe()}"
    assert s.resolve_rate == 1.0, s.describe()
    # every workload shape the model traced resolved from the warmed cache
    for shape in s.observed_shapes():
        assert planner.plan_cached(shape) is not None, shape
    np.testing.assert_allclose(routed, base, rtol=5e-2, atol=5e-2)
    print("stats:", s.describe())
    print("ALL_OK")
""")


@pytest.mark.slow
def test_serve_context_plan_hits_multidevice():
    """Serve-installed planner context yields plan hits for the model's
    workload shapes on a real multi-device mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", MULTIDEVICE_BODY], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (f"stdout:\n{proc.stdout}\n"
                                  f"stderr:\n{proc.stderr}")
    assert "ALL_OK" in proc.stdout


ONLINE_TUNE_BODY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import smoke_config
    from repro.deploy import Planner
    from repro.deploy.bucketing import BucketingPolicy
    from repro.deploy.plan import SOURCE_ANALYTIC, SOURCE_TUNED
    from repro.hw.config import (AcceleratorConfig, HBMConfig, NoCConfig,
                                 TileConfig)
    from repro.launch.mesh import make_mesh
    from repro.models import shard_ctx
    from repro.models.model import forward, init_params
    from repro.models.shard_ctx import GemmContext

    MINI = AcceleratorConfig(name="mini", grid=(4, 4),
                             tile=TileConfig(l1_bytes=4 * 1024 * 1024),
                             noc=NoCConfig(), hbm=HBMConfig(n_channels=8))
    cfg = smoke_config("gemma-2b")
    rng = np.random.default_rng(0)
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32)
    base = np.asarray(forward(params, toks, cfg), np.float32)

    # COLD planner: nothing warmed, transfers disabled — every traced shape
    # is absent from the cache and must resolve through the online
    # (analytic) tuning path, never the auto-dataflow fallback
    planner = Planner(MINI, elem_bytes=4, max_candidates=8,
                      policy=BucketingPolicy(max_transfers=0))
    mesh = make_mesh((2, 2), ("data", "model"))
    ctx = GemmContext(mesh=mesh, planner=planner)
    shard_ctx.set_gemm_context(ctx)
    routed = np.asarray(
        jax.jit(lambda p, t: forward(p, t, cfg))(params, toks), np.float32)
    shard_ctx.set_gemm_context(None)

    s = ctx.stats
    assert s.analytic > 0, "nothing resolved via the analytic variant"
    assert s.hits == 0 and s.bucketed == 0, s.describe()
    assert s.fallback == 0, f"silent degrade to auto: {s.describe()}"
    assert s.silent_degrades == 0, s.describe()
    assert s.resolve_rate == 1.0, s.describe()
    # every online-served shape is cached with `analytic` provenance
    pend = planner.pending_refinements
    assert pend, "online tunes queued nothing for refinement"
    for shape in s.observed_shapes():
        p = planner.cache.peek(shape, 4, MINI, planner.variant)
        assert p is not None and p.source == SOURCE_ANALYTIC, (shape, p)
    # background refinement full-tunes each and upgrades the provenance
    planner.refine_pending()
    for shape in pend:
        p = planner.cache.peek(shape, 4, MINI, planner.variant)
        assert p.source == SOURCE_TUNED, (shape, p.source)
    np.testing.assert_allclose(routed, base, rtol=5e-2, atol=5e-2)
    print("stats:", s.describe())
    print("ALL_OK")
""")


@pytest.mark.slow
def test_cold_serve_online_tunes_multidevice():
    """A routed multidevice trace with a COLD planner resolves every shape
    via the `analytic` online-tuning variant (recorded provenance, zero
    fallbacks/silent degrades) and background refinement upgrades each
    entry to `tuned`."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", ONLINE_TUNE_BODY], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (f"stdout:\n{proc.stdout}\n"
                                  f"stderr:\n{proc.stderr}")
    assert "ALL_OK" in proc.stdout
