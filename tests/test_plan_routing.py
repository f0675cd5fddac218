"""Plan-routed model matmuls (models/matmul.pmm + shard_ctx.GemmContext).

Covers the PR-2 contracts:
- with no gemm context, pmm is exactly `x @ w` and every block kind's
  forward is bit-for-bit unchanged (recording must not perturb numerics);
- the tied-embedding logits refactor (einsum -> x @ embed.T) is exact;
- dit_gemm derives the planner GEMMShape from flattened leading dims
  (regression: batched operands used to read a.shape[0]/b.shape[1] raw);
- a planner warmed from `gemm_workload` (the model's record-only trace of
  a step) serves every routed GEMM of the live trace of that step from a
  tuned or bucketed plan, for gqa/MLA/MoE/mamba2/xlstm/vlm and the
  encoder-decoder stack (seamless, incl. cross-attention K/V), prefill and
  decode;
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
from repro.deploy import Planner
from repro.deploy.warmup import warm_buckets
from repro.hw.config import AcceleratorConfig, HBMConfig, NoCConfig, TileConfig
from repro.launch.mesh import make_mesh
from repro.models import shard_ctx
from repro.models.matmul import pmm
from repro.models.model import decode_init, decode_step, forward, init_params
from repro.models.shard_ctx import GemmContext
from repro.models.workload import frontend_inputs, gemm_workload
from repro.obs import Tracer, dispatch_provenance, set_tracer

MINI = AcceleratorConfig(name="mini", grid=(4, 4),
                         tile=TileConfig(l1_bytes=4 * 1024 * 1024),
                         noc=NoCConfig(), hbm=HBMConfig(n_channels=8))

# one smoke arch per block kind: attention, MLA, MoE, the mamba2 hybrid,
# xLSTM, the VLM frontend and the encoder-decoder stack
BLOCK_KINDS = {
    "gqa": "gemma-2b",
    "mla": "deepseek-v2-236b",
    "moe": "deepseek-moe-16b",
    "mamba2": "zamba2-1.2b",
    "xlstm": "xlstm-1.3b",
    "vlm": "phi-3-vision-4.2b",
    "encdec": "seamless-m4t-medium",
}


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
    kwargs = {k: jnp.asarray(rng.standard_normal(v.shape), v.dtype)
              for k, v in frontend_inputs(cfg, 2, "prefill").items()}
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
# gemm_workload: the warm-up it names serves the live routed trace
# ---------------------------------------------------------------------------

def _trace_step(cfg, b: int, seq: int, kind: str) -> None:
    """Trace one step of `cfg` abstractly under whatever context is
    installed: `forward` over (b, seq) tokens, or `decode_step` against a
    cache of `seq` positions."""
    extra = frontend_inputs(cfg, b, kind)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    if kind == "decode":
        caches = jax.eval_shape(lambda: decode_init({}, cfg, b, seq))
        jax.eval_shape(
            lambda p, c, t, i, kw: decode_step(p, c, t, i, cfg, **kw),
            params, caches, jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32), extra)
    else:
        jax.eval_shape(lambda p, t, kw: forward(p, t, cfg, **kw), params,
                       jax.ShapeDtypeStruct((b, seq), jnp.int32), extra)


def _check_warmup_serves_live_trace(block_kind: str, step: str) -> None:
    """Warm a fresh planner from `gemm_workload` as the serve launcher
    does, then trace the same step live on a 1x1 mesh in this process:
    every GEMM the live trace routes resolves from a tuned or bucketed
    plan, nothing falls back, and the live trace runs every GEMM the
    workload named (the record-only trace left no cached, unrouted program
    behind for it to reuse)."""
    cfg = smoke_config(BLOCK_KINDS[block_kind])
    b, seq = 2, 16
    shapes = gemm_workload(cfg, b, seq, kind=step)
    assert shapes, "the step traced no GEMMs"
    planner = Planner(MINI, max_candidates=4)
    warm_buckets(planner, shapes)
    for shape in shapes:
        planner.plan(shape)
    ctx = GemmContext(mesh=make_mesh((1, 1), ("data", "model")),
                      planner=planner)
    tracer = Tracer(process_name="test")
    set_tracer(tracer)
    try:
        with shard_ctx.gemm_context(ctx):
            _trace_step(cfg, b, seq, step)
    finally:
        set_tracer(None)
    # attention (pattn.*) resolves online from its closed-form menu, which
    # `stats.analytic` counts too: the GEMM dispatches are read on their own
    gemm_rows = [d for d in dispatch_provenance(tracer)
                 if d["name"].startswith("pmm.")]
    assert gemm_rows, "no GEMM was routed"
    assert {d["provenance"] for d in gemm_rows} <= {"hit", "bucketed"}, \
        [d for d in gemm_rows if d["provenance"] not in ("hit", "bucketed")]
    assert ctx.stats.fallback == 0 and ctx.stats.unrouted == 0, \
        ctx.stats.describe()
    assert set(ctx.stats.observed_shapes()) == set(shapes)
    # a tied head is handed the embedding as stored, once a step
    assert (ctx.stats.read_transposed + ctx.stats.copied_transposed
            == int(cfg.tie_embeddings)), ctx.stats.describe()


@pytest.mark.parametrize("kind", sorted(BLOCK_KINDS))
def test_gemm_workload_warms_routed_prefill(kind):
    _check_warmup_serves_live_trace(kind, "prefill")


@pytest.mark.parametrize("kind", sorted(BLOCK_KINDS))
def test_gemm_workload_warms_routed_decode(kind):
    """Decode includes MLA's absorbed form (q-absorb / v-un-absorb
    contractions instead of the K/V up-projection) and the recurrent
    SSM/xLSTM mixers."""
    _check_warmup_serves_live_trace(kind, "decode")


@pytest.mark.parametrize("meshed", [False, True],
                         ids=["held_experts", "expert_sharded_mesh"])
def test_moe_geometry_prediction_matches_model(meshed):
    """`gemm_workload`'s expert GEMMs follow the activation mesh it is
    given, as moe.apply_moe does: with none, the held experts' grouped
    GEMMs at the static budget of tokens * k rows; under one, the
    capacity-bounded buffers of moe's own dispatch groups. The trace puts
    the mesh in place back afterwards."""
    from repro.models import moe
    cfg = smoke_config("deepseek-moe-16b")
    b, s = 2, 16
    mesh = make_mesh((1, 1), ("data", "model"))
    cap = moe._capacity(b * s // moe._dp_groups(b * s, mesh), cfg)
    held = b * s * cfg.moe_top_k
    rows, other = (cap, held) if meshed else (held, cap)
    shapes = set(gemm_workload(cfg, b, s, mesh=mesh if meshed else None))
    assert shard_ctx.get_mesh() is None
    d, f = cfg.d_model, cfg.moe_d_ff
    assert {GEMMShape(rows, f, d), GEMMShape(rows, d, f)} <= shapes
    assert not {GEMMShape(other, f, d), GEMMShape(other, d, f)} & shapes


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
    planner.batch_tune(gemm_workload(cfg, 2, 16, kind="prefill"))
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
    from repro.deploy import Planner
    from repro.hw.config import (AcceleratorConfig, HBMConfig, NoCConfig,
                                 TileConfig)
    from repro.launch.mesh import make_mesh
    from repro.models import shard_ctx
    from repro.models.model import forward, init_params
    from repro.models.shard_ctx import GemmContext
    from repro.models.workload import gemm_workload

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
    planner.batch_tune(gemm_workload(cfg, 4, 16, kind="prefill"))
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
