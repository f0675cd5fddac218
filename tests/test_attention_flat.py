"""FlatAttention (the fused attention dataflow) + the attention-path
bugfix sweep that rode along with it.

Covers the PR-10 contracts:
- `flat_attention` (merge and ring compositions) matches the `_sdpa`
  oracle — forward AND grads — across GQA/MQA, non-causal, dv != d, and
  decode (q_positions + kv_len) geometries;
- `lower_attention` resolves every fallback chain with a machine-readable
  reason and never lands on the silent `auto` mode (device-free);
- the planner resolves AttnShapes like GEMMs: closed-form candidates,
  tuned/analytic sources, serialization + cache round-trips, and
  `shapes_for` never offers an attention plan as a bucketing seed;
- satellite regressions: chunked_sdpa's prime-length tail (pad + mask,
  not a divisor walk), the decode branch threading the caller's `causal`
  flag, pmm recording non-routable operands before bailing, and MLA's
  absorbed-form per-head accounting (count = n_heads);
- a routed multidevice proof (subprocess, slow): gemma-2b (GQA) and
  deepseek-v2 (MLA) decode through the fused mode with resolve rate 1.0
  and zero silent degrades.
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
from repro.core import lower
from repro.core.attention import attn_candidates, attn_tune, flat_attention
from repro.core.lower import lower_attention
from repro.core.schedule import (ATTN_DATAFLOW, AttnSchedule, AttnShape,
                                 GEMMShape)
from repro.deploy import (PlanCache, Planner, schedule_from_dict,
                          schedule_to_dict)
from repro.deploy.plan import SOURCE_ANALYTIC, SOURCE_TUNED
from repro.hw.config import AcceleratorConfig, HBMConfig, NoCConfig, TileConfig
from repro.launch.mesh import make_mesh
from repro.models import shard_ctx
from repro.models.attention import (_chunk, _sdpa, chunked_sdpa,
                                    gqa_attention, gqa_params, mla_attention,
                                    mla_params)
from repro.models.matmul import pattn, pmm
from repro.models.shard_ctx import GemmContext, GemmStats
from repro.models.workload import gemm_workload

MINI = AcceleratorConfig(name="mini", grid=(4, 4),
                         tile=TileConfig(l1_bytes=4 * 1024 * 1024),
                         noc=NoCConfig(), hbm=HBMConfig(n_channels=8))


class FakeMesh:            # lowering only reads .shape[axis]
    shape = {"data": 4, "model": 4}


def _shape(b=2, sq=64, skv=64, h=8, hkv=4, d=16, dv=16, causal=True):
    return AttnShape(b=b, sq=sq, skv=skv, h=h, hkv=hkv, d=d, dv=dv,
                     causal=causal)


def _sched(shape, comp="merge", kv_chunk=16):
    return AttnSchedule(shape=shape, composition=comp, kv_chunk=kv_chunk)


def _qkv(rng, b, sq, skv, h, hkv, d, dv, dtype=jnp.float32):
    return (jnp.asarray(rng.standard_normal((b, sq, h, d)), dtype),
            jnp.asarray(rng.standard_normal((b, skv, hkv, d)), dtype),
            jnp.asarray(rng.standard_normal((b, skv, hkv, dv)), dtype))


# -- lowering: the fallback matrix is machine-readable, never silent ---------

def test_lower_merge_clean():
    ep = lower_attention(_sched(_shape()), FakeMesh(), "data", "model")
    assert ep.mode == "flat_merge" and not ep.reasons()
    assert ep.kwargs["composition"] == "merge"
    assert ep.kwargs["head_shard"] is True      # h=8, hkv=4 both divide dn=4


def test_lower_ring_clean():
    ep = lower_attention(_sched(_shape(), comp="ring"), FakeMesh(),
                         "data", "model")
    assert ep.mode == "flat_ring" and not ep.reasons()


def test_lower_ring_seq_indivisible_demotes_to_merge():
    ep = lower_attention(_sched(_shape(sq=63), comp="ring"), FakeMesh(),
                         "data", "model")
    assert ep.mode == "flat_merge"
    assert lower.ATTN_SEQ_NOT_DIVISIBLE in ep.reasons()
    assert ep.kwargs["composition"] == "merge"


def test_lower_kv_indivisible_demotes_to_unfused():
    ep = lower_attention(_sched(_shape(skv=63)), FakeMesh(), "data", "model")
    assert ep.mode == "unfused_attn"
    assert lower.ATTN_KV_NOT_DIVISIBLE in ep.reasons()
    assert ep.kwargs == {}


@pytest.mark.parametrize("grid", [(1, 1), (4, 4)], ids=["one", "4x4"])
def test_lower_kv_copy_takes_unfused_on_one_device(grid):
    # K/V built only for the fused executor (MLA's latent view): on one
    # device it distributes nothing, so the call degrades, with its reason
    import types
    mesh = types.SimpleNamespace(shape=dict(zip(("data", "model"), grid)))
    ep = lower_attention(_sched(_shape(hkv=1)), mesh, "data", "model",
                         kv_copy=True)
    if grid == (1, 1):
        assert ep.mode == "unfused_attn" and ep.kwargs == {}
        assert ep.reasons() == (lower.ATTN_ONE_DEVICE_COPY,)
        plain = lower_attention(_sched(_shape(hkv=1)), mesh, "data", "model")
        assert plain.mode == "flat_merge" and not plain.reasons()
    else:
        assert ep.mode == "flat_merge" and not ep.reasons()


def test_lower_heads_replicated_is_kwarg_demotion():
    # hkv=2 neither divides dn=4 nor is 1 -> replicate heads, mode unchanged
    ep = lower_attention(_sched(_shape(hkv=2)), FakeMesh(), "data", "model")
    assert ep.mode == "flat_merge"
    assert lower.ATTN_HEADS_REPLICATED in ep.reasons()
    assert ep.kwargs["head_shard"] is False


def test_lower_mqa_heads_shard():
    # hkv=1 is fully replicable, so query heads still shard
    ep = lower_attention(_sched(_shape(hkv=1)), FakeMesh(), "data", "model")
    assert ep.mode == "flat_merge" and not ep.reasons()
    assert ep.kwargs["head_shard"] is True


def test_lower_unknown_composition():
    import types
    sched = types.SimpleNamespace(composition="zigzag", kv_chunk=16,
                                  shape=_shape())
    ep = lower_attention(sched, FakeMesh(), "data", "model")
    assert ep.mode == "flat_merge"
    assert lower.ATTN_UNKNOWN_COMPOSITION in ep.reasons()


@pytest.mark.parametrize("kwargs", [
    {}, {"sq": 63}, {"skv": 63}, {"hkv": 2},
])
def test_lower_attention_never_lands_on_auto(kwargs):
    """The degrade target is the named unfused path, never silent auto."""
    for comp in ("merge", "ring"):
        ep = lower_attention(_sched(_shape(**kwargs), comp=comp),
                             FakeMesh(), "data", "model")
        assert not ep.degraded
        assert ep.mode in ("flat_merge", "flat_ring", "unfused_attn")


def test_attention_vocabulary_registered():
    """Modes and reasons live in the pinned registries (test_docs pins the
    registries into docs/dataflows.md, so this transitively pins the doc)."""
    for mode in ("flat_merge", "flat_ring", "unfused_attn"):
        assert mode in lower.EXEC_MODES
    for reason in (lower.ATTN_SEQ_NOT_DIVISIBLE, lower.ATTN_KV_NOT_DIVISIBLE,
                   lower.ATTN_HEADS_REPLICATED,
                   lower.ATTN_UNKNOWN_COMPOSITION):
        assert reason in lower.REASONS


# -- candidates + tuning ------------------------------------------------------

def test_attn_candidates_legality():
    # skv must shard over the row axis
    assert attn_candidates(_shape(skv=63), MINI) == ()
    # decode (sq=1) gets merge only; prefill with divisible sq adds ring
    decode = attn_candidates(_shape(sq=1, skv=4096, hkv=1), MINI)
    assert decode and all(c.composition == "merge" for c in decode)
    prefill = attn_candidates(_shape(sq=256, skv=256), MINI)
    assert {c.composition for c in prefill} == {"merge", "ring"}
    for c in prefill:
        assert c.shape == _shape(sq=256, skv=256)


def test_attn_tune_prices_and_picks():
    shape = _shape(sq=256, skv=256)
    res = attn_tune(shape, MINI)
    assert res.schedule in attn_candidates(shape, MINI)
    assert res.report.total_time > 0
    assert res.candidates_tried == len(attn_candidates(shape, MINI))
    with pytest.raises(RuntimeError):
        attn_tune(_shape(skv=63), MINI)


# -- planner + cache: attention shapes resolve like GEMMs --------------------

def test_attn_schedule_serialization_roundtrip():
    sched = attn_tune(_shape(), MINI).schedule
    d = schedule_to_dict(sched)
    assert d["kind"] == "attention"
    assert schedule_from_dict(d) == sched


def test_planner_attention_sources_and_cache(tmp_path):
    cache = PlanCache(str(tmp_path))
    planner = Planner(MINI, cache=cache)
    shape = _shape()

    # cold dispatch path: online analytic pricing
    analytic = planner.plan_cached(shape)
    assert analytic is not None and analytic.source == SOURCE_ANALYTIC
    assert analytic.schedule.shape == shape
    assert analytic.schedule.dataflow == ATTN_DATAFLOW

    # warm-up path upgrades to tuned; re-lookup serves the cached entry
    tuned = planner.plan(shape)
    assert tuned.source == SOURCE_TUNED
    assert planner.plan_cached(shape).source == SOURCE_TUNED

    # attention plans persist but never seed GEMM bucketing transfers
    planner.plan(GEMMShape(256, 256, 256))
    assert list(cache.shapes_for(planner.elem_bytes, MINI,
                                 planner.variant)) == \
        [GEMMShape(256, 256, 256)]

    # a fresh planner over the same directory reloads the attention plan
    again = Planner(MINI, cache=PlanCache(str(tmp_path)))
    assert again.plan_cached(shape).schedule == tuned.schedule

    # attention shapes are never queued for background refinement
    assert shape not in planner.pending_refinements


def test_gemm_stats_attention_roundtrip():
    stats = GemmStats()
    stats.record_attn("attn.sdpa", _shape())
    stats.record_attn("attn.sdpa", _shape())
    stats.record_attn("mla.decode", _shape(sq=1, hkv=1, causal=True))
    stats.record("attn.q", GEMMShape(64, 64, 64))
    stats.unroutable += 1
    d = stats.to_dict()
    assert d["unroutable"] == 1
    rt = GemmStats.from_dict(d)
    assert rt.to_dict() == d
    assert rt.attn_observed[("attn.sdpa", _shape())] == 2
    # attention shapes never leak into the GEMM-observed workload (its
    # consumers sort on (m, n, k) and rebuild GEMMShape(*shape))
    assert stats.observed_shapes() == [GEMMShape(64, 64, 64)]


def test_pattn_plan_miss_degrades_to_named_unfused():
    """No planner -> counted fallback, the caller's unfused closure runs."""
    mesh = make_mesh((1, 1), ("data", "model"))
    ctx = GemmContext(mesh=mesh, planner=None)
    rng = np.random.default_rng(0)
    q, k, v = _qkv(rng, 2, 8, 8, 4, 2, 16, 16)
    ran = []
    with shard_ctx.gemm_context(ctx):
        out = pattn(q, k, v, causal=True, tag="attn.sdpa",
                    unfused=lambda: ran.append(1) or _sdpa(q, k, v,
                                                           causal=True))
    assert ran == [1]
    assert ctx.stats.fallback == 1 and ctx.stats.resolve_rate == 0.0
    np.testing.assert_array_equal(out, _sdpa(q, k, v, causal=True))


# -- fused executor vs the _sdpa oracle (single device; multidevice parity
#    runs in the subprocess proof below) ------------------------------------

@pytest.mark.parametrize("case", [
    dict(h=8, hkv=2, causal=True),                    # GQA
    dict(h=8, hkv=1, dv=24, causal=True),             # MQA, dv != d
    dict(h=4, hkv=4, causal=False),                   # MHA, non-causal
])
def test_flat_attention_matches_sdpa(case):
    causal = case.pop("causal")
    rng = np.random.default_rng(3)
    shape = _shape(causal=causal, **case)
    q, k, v = _qkv(rng, shape.b, shape.sq, shape.skv, shape.h, shape.hkv,
                   shape.d, shape.dv)
    mesh = make_mesh((1, 1), ("data", "model"))
    ep = lower_attention(_sched(shape), mesh, "data", "model")
    assert ep.mode == "flat_merge"
    got = flat_attention(q, k, v, mesh, ep, causal=causal)
    ref = _sdpa(q, k, v, causal=causal)
    np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4)


def test_flat_attention_decode_positions_and_kv_len():
    rng = np.random.default_rng(4)
    shape = _shape(b=2, sq=1, skv=16, h=8, hkv=2)
    q, k, v = _qkv(rng, 2, 1, 16, 8, 2, 16, 16)
    qpos = jnp.array([5], jnp.int32)
    klen = jnp.array([6, 9], jnp.int32)
    mesh = make_mesh((1, 1), ("data", "model"))
    ep = lower_attention(_sched(shape), mesh, "data", "model")
    got = flat_attention(q, k, v, mesh, ep, causal=True, q_positions=qpos,
                         kv_len=klen)
    ref = _sdpa(q, k, v, causal=True, q_positions=qpos, kv_len=klen)
    np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4)


def test_flat_attention_grads_match_sdpa():
    rng = np.random.default_rng(5)
    shape = _shape(h=8, hkv=2)
    q, k, v = _qkv(rng, shape.b, shape.sq, shape.skv, shape.h, shape.hkv,
                   shape.d, shape.dv)
    mesh = make_mesh((1, 1), ("data", "model"))
    ep = lower_attention(_sched(shape), mesh, "data", "model")
    g_ref = jax.grad(lambda q, k, v: _sdpa(q, k, v, causal=True).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(
        lambda q, k, v: flat_attention(q, k, v, mesh, ep,
                                       causal=True).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for ref, got in zip(g_ref, g_got):
        np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4)


# -- satellite 1: chunked_sdpa prime-length tail (pad + mask) ----------------

def test_chunk_is_a_clamp_not_a_divisor_walk():
    # the old fit() walked divisors down: _chunk(997, 256) returned 1 and
    # the flash path degenerated to one column per step
    assert _chunk(997, 256) == 256
    assert _chunk(97, 32) == 32
    assert _chunk(5, 256) == 5


@pytest.mark.parametrize("causal", [True, False])
def test_chunked_sdpa_prime_seq_parity(causal):
    rng = np.random.default_rng(6)
    q, k, v = _qkv(rng, 2, 97, 97, 4, 2, 16, 16)
    got = chunked_sdpa(q, k, v, causal=causal, chunk_q=32, chunk_k=32)
    ref = _sdpa(q, k, v, causal=causal)
    np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4)


def test_chunked_sdpa_prime_seq_grads():
    rng = np.random.default_rng(7)
    q, k, v = _qkv(rng, 1, 97, 97, 2, 2, 8, 8)
    g_ref = jax.grad(lambda q, k, v: _sdpa(q, k, v, causal=True).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    g_got = jax.grad(
        lambda q, k, v: chunked_sdpa(q, k, v, causal=True, chunk_q=32,
                                     chunk_k=32).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for ref, got in zip(g_ref, g_got):
        np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4)


def test_chunked_sdpa_ragged_kv_only():
    rng = np.random.default_rng(8)
    q, k, v = _qkv(rng, 2, 64, 97, 4, 1, 16, 16)
    got = chunked_sdpa(q, k, v, causal=False, chunk_q=32, chunk_k=32)
    ref = _sdpa(q, k, v, causal=False)
    np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4)


# -- satellite 2: the decode branch threads the caller's causal flag ---------

def _gqa_decode_fixture():
    cfg = smoke_config("gemma-2b")
    rng = np.random.default_rng(9)
    p = gqa_params(jax.random.PRNGKey(0), cfg)
    cache = {                                   # one layer, (L, B, Hkv, S, D)
        "k": jnp.asarray(rng.standard_normal(
            (1, 1, cfg.n_kv_heads, 16, cfg.hd)), cfg.dtype),
        "v": jnp.asarray(rng.standard_normal(
            (1, 1, cfg.n_kv_heads, 16, cfg.hd)), cfg.dtype),
        "index": jnp.asarray([8], jnp.int32),
    }
    x = jnp.asarray(rng.standard_normal((1, 1, cfg.d_model)), cfg.dtype)
    return cfg, p, cache, x


def test_decode_causal_flag_is_threaded():
    """Scoring a query at a position EARLIER than the cache frontier must
    see different attention under causal=True (keys beyond the position
    masked) vs causal=False (whole valid prefix visible). The old branch
    hard-coded causal=True, making the two bitwise identical."""
    cfg, p, cache, x = _gqa_decode_fixture()
    positions = jnp.array([3], jnp.int32)       # < cache index 8
    out_c, _ = gqa_attention(p, x, cfg, positions, cache=dict(cache),
                             causal=True)
    out_nc, _ = gqa_attention(p, x, cfg, positions, cache=dict(cache),
                              causal=False)
    assert np.isfinite(np.asarray(out_c, np.float32)).all()
    assert np.isfinite(np.asarray(out_nc, np.float32)).all()
    assert not np.allclose(np.asarray(out_c, np.float32),
                           np.asarray(out_nc, np.float32))


def test_cache_and_kv_input_are_mutually_exclusive():
    cfg, p, cache, x = _gqa_decode_fixture()
    enc = jnp.zeros((1, 4, cfg.d_model), cfg.dtype)
    with pytest.raises(ValueError, match="mutually"):
        gqa_attention(p, x, cfg, jnp.array([8], jnp.int32), cache=cache,
                      kv_input=enc)


# -- satellite 3: pmm records non-routable operands before bailing -----------

def test_pmm_records_batched_weight_before_bailing():
    x = jnp.asarray(np.random.default_rng(10).standard_normal((2, 3, 4)),
                    jnp.float32)
    w = jnp.asarray(np.random.default_rng(11).standard_normal((2, 4, 5)),
                    jnp.float32)
    ctx = GemmContext(mesh=None)                # record-only
    with shard_ctx.gemm_context(ctx):
        out = pmm(x, w, tag="bmm")
    np.testing.assert_array_equal(out, x @ w)   # bitwise: stays out of the way
    # the old early-return skipped record(): the observed workload silently
    # undercounted every batched-weight einsum routed through pmm
    assert ctx.stats.observed == {("bmm", GEMMShape(6, 5, 4)): 1}
    assert ctx.stats.unroutable == 1


# -- satellite 4: MLA absorbed-form per-head accounting ----------------------

def test_mla_absorbed_decode_counts_per_head():
    cfg = smoke_config("deepseek-v2-236b")
    rng = np.random.default_rng(12)
    p = mla_params(jax.random.PRNGKey(1), cfg)
    b, max_len = 2, 8
    cache = {                                   # one layer
        "c_kv": jnp.asarray(rng.standard_normal(
            (1, b, max_len, cfg.kv_lora_rank)), cfg.dtype),
        "k_rope_t": jnp.asarray(rng.standard_normal(
            (1, b, cfg.rope_head_dim, max_len)), cfg.dtype),
        "index": jnp.asarray([4], jnp.int32),
    }
    x = jnp.asarray(rng.standard_normal((b, 1, cfg.d_model)), cfg.dtype)
    ctx = GemmContext(mesh=None)                # record-only
    with shard_ctx.gemm_context(ctx):
        mla_attention(p, x, cfg, jnp.array([4], jnp.int32), cache=cache)
    r, dn = cfg.kv_lora_rank, cfg.nope_head_dim
    # the absorbed einsums are n_heads independent per-head contractions;
    # a single record undercounted the decode workload ~n_heads x
    assert ctx.stats.observed[("mla.q_absorb", GEMMShape(b, r, dn))] \
        == cfg.n_heads
    assert ctx.stats.observed[("mla.v_unabsorb", GEMMShape(b, dn, r))] \
        == cfg.n_heads
    # and the decode step's workload names them once each (multiplicity
    # lives in the observed counts)
    workload = gemm_workload(cfg, b, max_len, kind="decode")
    assert GEMMShape(b, r, dn) in workload
    assert GEMMShape(b, dn, r) in workload
    # the attention problem itself lands in the attention workload
    assert any(tag == "mla.decode"
               for (tag, _) in ctx.stats.attn_observed)


# -- routed multidevice proof (subprocess; slow) -----------------------------

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

ROUTED_ATTENTION_BODY = textwrap.dedent("""
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
    from repro.models.model import decode_init, decode_step, init_params
    from repro.models.shard_ctx import GemmContext
    from repro.models.workload import gemm_workload

    MINI = AcceleratorConfig(name="mini", grid=(4, 1),
                             tile=TileConfig(l1_bytes=4 * 1024 * 1024),
                             noc=NoCConfig(), hbm=HBMConfig(n_channels=8))

    for name in ("gemma-2b", "deepseek-v2-236b"):
        cfg = smoke_config(name)
        params = init_params(jax.random.PRNGKey(0), cfg)
        toks = jnp.zeros((4, 1), jnp.int32)

        # unrouted baseline
        caches = decode_init(params, cfg, batch=4, max_len=8)
        base, _ = decode_step(params, caches, toks, jnp.asarray(0, jnp.int32),
                              cfg)
        base = np.asarray(base, np.float32)

        planner = Planner(MINI, elem_bytes=4, max_candidates=8)
        planner.batch_tune(gemm_workload(cfg, 4, 8, kind="decode"),
                           skip_illegal=True)
        mesh = make_mesh((4, 1), ("data", "model"))
        ctx = GemmContext(mesh=mesh, planner=planner)
        shard_ctx.set_gemm_context(ctx)
        caches = decode_init(params, cfg, batch=4, max_len=8)
        routed, _ = decode_step(params, caches, toks,
                                jnp.asarray(0, jnp.int32), cfg)
        routed = np.asarray(routed, np.float32)
        shard_ctx.set_gemm_context(None)

        s = ctx.stats
        assert s.routed > 0, name
        assert s.fallback == 0, (name, s.describe())
        assert s.resolve_rate == 1.0, (name, s.describe())
        assert s.silent_degrades == 0, (name, s.describe())
        # decode (sq=1) lowers to the merge composition of the fused mode
        assert s.modes.get("flat_merge", 0) > 0, (name, s.modes)
        assert s.modes.get("unfused_attn", 0) == 0, (name, s.modes)
        assert s.attn_observed, name
        np.testing.assert_allclose(routed, base, rtol=5e-2, atol=5e-2)
        print(name, "modes:", s.modes)
    print("ALL_OK")
""")


@pytest.mark.slow
def test_routed_attention_multidevice():
    """GQA and MLA decode route through the fused attention mode on a real
    multi-device mesh: resolve rate 1.0, zero plan misses, zero silent
    degrades, and the routed logits match the unrouted baseline."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", ROUTED_ATTENTION_BODY],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, (f"stdout:\n{proc.stdout}\n"
                                  f"stderr:\n{proc.stderr}")
    assert "ALL_OK" in proc.stdout
