"""Deployment-plan subsystem: artifact round-trip, cache semantics,
fingerprint invalidation, bucketed-transfer quality vs a fresh tune, and the
planner's warm-path contract (no enumeration on a hit)."""
import dataclasses
import json
import os
import time
from types import SimpleNamespace

import pytest

from repro.core.autotuner import enumerate_candidates, tune, tune_cached
from repro.core.layout import optimal_layout
from repro.core.lower import lower_schedule
from repro.core.remap import ClusterRemap
from repro.core.schedule import GEMMShape, Schedule, Tiling, build_program
from repro.deploy import (BucketingPolicy, DeploymentPlan, PlanCache, Planner,
                          SOURCE_BUCKETED, SOURCE_TUNED, adapt, bucket_of,
                          hw_fingerprint, plan_from_tuning)
from repro.hw.config import AcceleratorConfig, HBMConfig, NoCConfig, TileConfig
from repro.sim.perf import estimate

MINI = AcceleratorConfig(name="mini", grid=(4, 4),
                         tile=TileConfig(l1_bytes=4 * 1024 * 1024),
                         noc=NoCConfig(), hbm=HBMConfig(n_channels=8))
MINI_BIG_L1 = AcceleratorConfig(name="mini-big-l1", grid=(4, 4),
                                tile=TileConfig(l1_bytes=8 * 1024 * 1024),
                                noc=NoCConfig(), hbm=HBMConfig(n_channels=8))

SHAPE = GEMMShape(256, 256, 256)


def make_plan(shape=SHAPE, hw=MINI, **tune_kw):
    res = tune(shape, hw, elem_bytes=4, max_candidates=16, **tune_kw)
    return plan_from_tuning(shape, hw, res.schedule, res.report,
                            candidates_tried=res.candidates_tried)


def make_planner(hw=MINI, cache=None, **kw):
    return Planner(hw, cache=cache, elem_bytes=4, max_candidates=16, **kw)


# ---------------------------------------------------------------------------
# plan artifact
# ---------------------------------------------------------------------------

def test_plan_json_round_trip():
    plan = make_plan()
    back = DeploymentPlan.from_json(plan.to_json())
    assert back.schedule == plan.schedule
    assert back.report == plan.report
    assert back.hw_digest == plan.hw_digest
    assert back.source == SOURCE_TUNED


def test_plan_round_trip_with_remap_and_layouts():
    sched = Schedule(SHAPE, Tiling(2, 8, 1, tk=64), "summa",
                     remap=ClusterRemap((4, 4), (2, 8)),
                     layouts={"A": optimal_layout((256, 256), 128, 32, 8)},
                     store_stages=4, reduce_owner="round_robin",
                     elem_bytes=4)
    rep = estimate(build_program(sched, MINI), MINI)
    plan = plan_from_tuning(SHAPE, MINI, sched, rep)
    back = DeploymentPlan.from_json(plan.to_json())
    assert back.schedule == sched
    # the deserialized schedule must still build
    assert build_program(back.schedule, MINI).supersteps


def test_plan_schema_version_rejected():
    d = make_plan().to_dict()
    d["schema_version"] = 999
    with pytest.raises(ValueError, match="schema version"):
        DeploymentPlan.from_dict(d)


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------

def test_cache_hit_miss_semantics():
    cache = PlanCache()
    assert cache.get(SHAPE, 4, MINI) is None
    assert cache.stats.misses == 1
    plan = make_plan()
    cache.put(plan)
    got = cache.get(SHAPE, 4, MINI)
    assert got is plan
    assert cache.stats.hits == 1
    # different elem_bytes is a different tuning problem
    assert cache.get(SHAPE, 1, MINI) is None


def test_cache_persistence_round_trip(tmp_path):
    cache = PlanCache(str(tmp_path))
    cache.put(make_plan())
    reloaded = PlanCache(str(tmp_path))
    got = reloaded.peek(SHAPE, 4, MINI)
    assert got is not None
    assert got.schedule == cache.peek(SHAPE, 4, MINI).schedule


def test_cache_ignores_corrupt_and_foreign_files(tmp_path):
    cache = PlanCache(str(tmp_path))
    cache.put(make_plan())
    (tmp_path / "garbage.plan.json").write_text("{not json")
    stale = make_plan().to_dict()
    stale["schema_version"] = 999
    (tmp_path / "stale.plan.json").write_text(json.dumps(stale))
    reloaded = PlanCache(str(tmp_path))
    assert len(reloaded) == 1


def test_hw_fingerprint_invalidation():
    cache = PlanCache()
    cache.put(make_plan(hw=MINI))
    # same grid, different L1 capacity -> different legality space -> miss
    assert hw_fingerprint(MINI) != hw_fingerprint(MINI_BIG_L1)
    assert cache.get(SHAPE, 4, MINI_BIG_L1) is None
    assert cache.get(SHAPE, 4, MINI) is not None


# ---------------------------------------------------------------------------
# autotuner integration
# ---------------------------------------------------------------------------

def test_enumerate_candidates_deduped():
    seen = set()
    for sched in enumerate_candidates(SHAPE, MINI, elem_bytes=4,
                                      max_candidates=256):
        key = (sched.tiling, sched.dataflow, sched.acc_bytes)
        assert key not in seen, f"duplicate candidate {sched.describe()}"
        seen.add(key)


def test_tune_cached_skips_enumeration_on_hit():
    cache = PlanCache()
    cold = tune_cached(SHAPE, MINI, cache, elem_bytes=4, max_candidates=16)
    warm = tune_cached(SHAPE, MINI, cache, elem_bytes=4, max_candidates=16)
    assert cold.candidates_tried > 0
    assert warm.candidates_tried == 0
    assert warm.schedule == cold.schedule
    assert warm.report.total_time == cold.report.total_time


# ---------------------------------------------------------------------------
# planner: warm path + bucketing
# ---------------------------------------------------------------------------

def test_planner_warm_path_no_enumeration(monkeypatch):
    planner = make_planner()
    cold = planner.plan(SHAPE)
    # a warm hit must never reach the autotuner
    import repro.deploy.planner as planner_mod

    def boom(*a, **k):
        raise AssertionError("tune called on the warm path")

    monkeypatch.setattr(planner_mod, "tune", boom)
    warm = planner.plan(SHAPE)
    assert warm is cold


def test_planner_warm_speedup():
    planner = make_planner()
    t0 = time.perf_counter()
    planner.plan(SHAPE)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(10):
        planner.plan(SHAPE)
    warm = (time.perf_counter() - t0) / 10
    assert cold / warm >= 10, f"warm path only {cold / warm:.1f}x faster"


def test_bucket_of_rounds_up_pow2():
    policy = BucketingPolicy(dim_cap=4096)
    assert bucket_of(GEMMShape(192, 256, 300), policy) == \
        GEMMShape(256, 256, 512)
    assert bucket_of(GEMMShape(100000, 8, 4096), policy) == \
        GEMMShape(4096, 8, 4096)


def test_adapt_reclamps_tk():
    src = tune(GEMMShape(256, 256, 512), MINI, elem_bytes=4,
               max_candidates=16).schedule
    # K shrinks to a value the tuned tk may not divide: adapt must re-derive
    adapted = adapt(src, GEMMShape(256, 256, 192), MINI)
    assert adapted is not None
    assert build_program(adapted, MINI).supersteps


def test_bucketed_lookup_within_tolerance_of_fresh_tune():
    planner = make_planner()
    planner.batch_tune([GEMMShape(256, 256, 256), GEMMShape(256, 256, 512),
                        GEMMShape(512, 256, 256)])
    probes = [GEMMShape(192, 256, 256), GEMMShape(256, 192, 256),
              GEMMShape(224, 224, 256), GEMMShape(256, 256, 384)]
    bucketed_ok = 0
    for probe in probes:
        plan = planner.plan(probe)
        assert build_program(plan.schedule, MINI).supersteps   # legal
        fresh = tune(probe, MINI, elem_bytes=4, max_candidates=16)
        ratio = plan.report.total_time / fresh.report.total_time
        assert ratio <= 1.0 + planner.policy.tolerance + 1e-9, (
            f"{probe}: bucketed plan {ratio:.2f}x the fresh tune")
        if plan.source == SOURCE_BUCKETED:
            bucketed_ok += 1
    # the acceptance bar: at least 3 probes actually served from buckets
    assert bucketed_ok >= 3


def test_bad_transfer_falls_back_to_full_tune():
    planner = make_planner()
    planner.plan(GEMMShape(512, 512, 256))
    # far-off aspect ratio: either no transfer attempt survives the expected-
    # time guard, or the transfer is genuinely within tolerance.
    plan = planner.plan(GEMMShape(32, 512, 256))
    fresh = tune(GEMMShape(32, 512, 256), MINI, elem_bytes=4,
                 max_candidates=16)
    assert plan.report.total_time <= \
        (1.0 + planner.policy.tolerance) * fresh.report.total_time


def test_restricted_planner_does_not_clobber_unrestricted(tmp_path):
    cache = PlanCache(str(tmp_path))
    p_free = make_planner(cache=cache)
    free_plan = p_free.plan(SHAPE)
    p_base = make_planner(cache=cache, dataflows=["baseline"])
    base_plan = p_base.plan(SHAPE)
    assert base_plan.schedule.dataflow == "baseline"
    # both variants coexist: each planner hits its own entry
    assert p_free.plan(SHAPE) is free_plan
    assert p_base.plan(SHAPE) is base_plan
    # and both survive a reload from disk
    reloaded = PlanCache(str(tmp_path))
    assert len(reloaded) == 2


def test_empty_dataflows_treated_as_unrestricted():
    # [] means 'unrestricted' to the tuner; the cache layers must agree or
    # every plan() call would re-tune forever.
    planner = make_planner(dataflows=[])
    assert planner.variant == ""
    p1 = planner.plan(SHAPE)
    puts = planner.cache.stats.puts
    assert planner.plan(SHAPE) is p1
    assert planner.cache.stats.puts == puts


def test_transfers_only_seed_from_tuned_plans():
    planner = make_planner()
    # a bucketed-source entry at the bucket shape must NOT seed transfers
    # (chained transfers would compound the tolerance loss per generation)
    res = tune(SHAPE, MINI, elem_bytes=4, max_candidates=16)
    planner.cache.put(plan_from_tuning(SHAPE, MINI, res.schedule, res.report,
                                       source=SOURCE_BUCKETED))
    plan = planner.plan(GEMMShape(224, 224, 256))
    assert plan.source == SOURCE_TUNED


def test_refinement_upgrades_bucketed_entries():
    planner = make_planner()
    planner.plan(GEMMShape(256, 256, 256))
    probe = GEMMShape(224, 224, 256)
    plan = planner.plan(probe)
    if plan.source != SOURCE_BUCKETED:
        pytest.skip("probe was not served from a bucket on this config")
    assert probe in planner.pending_refinements
    records = planner.refine_pending()
    assert [s for s, _, _ in records] == [probe]
    assert not planner.pending_refinements
    refined = planner.cache.peek(probe, 4, MINI)
    assert refined.source == SOURCE_TUNED
    assert refined.report.total_time <= plan.report.total_time


def test_refine_async_executor():
    from concurrent.futures import ThreadPoolExecutor
    planner = make_planner()
    planner.plan(GEMMShape(256, 256, 256))
    plan = planner.plan(GEMMShape(192, 256, 256))
    if plan.source != SOURCE_BUCKETED:
        pytest.skip("probe was not served from a bucket on this config")
    with ThreadPoolExecutor(max_workers=1) as ex:
        futures = planner.refine_async(ex)
        results = [f.result() for f in futures]
    assert results and not planner.pending_refinements


# ---------------------------------------------------------------------------
# dispatch + workload extraction
# ---------------------------------------------------------------------------

def test_lower_schedule_mapping():
    """The deploy-facing contract of the schedule->mesh lowering: tuned
    dataflows resolve to their mesh modes (tests/test_lowering.py covers the
    full fallback-reason matrix)."""
    mesh_sq = SimpleNamespace(shape={"data": 2, "model": 2})
    mesh_rect = SimpleNamespace(shape={"data": 1, "model": 4})

    def sched(df, owner="first", gk=1):
        return Schedule(SHAPE, Tiling(4, 4, gk, tk=64), df,
                        reduce_owner=owner)

    assert lower_schedule(sched("summa"), mesh_sq).mode == "summa"
    assert lower_schedule(sched("systolic"), mesh_sq).mode == "cannon"
    ep = lower_schedule(sched("systolic"), mesh_rect)
    assert ep.mode == "summa" and "non_square_systolic" in ep.reasons()
    assert lower_schedule(sched("baseline"), mesh_sq).mode == "allgather"
    # the tuned 3-D grid survives: gk=2 factors out of the model axis
    ep = lower_schedule(sched("splitk_summa", "round_robin", gk=2), mesh_sq)
    assert ep.mode == "splitk_summa" and ep.kwargs["scatter"] is True
    assert ep.axes["k"] == "splitk" and not ep.fallbacks
    ep = lower_schedule(sched("splitk_summa", "first", gk=2), mesh_sq)
    assert ep.kwargs["scatter"] is False
    # a k-grid that factors into neither axis collapses to 1-D split-K,
    # with the reason recorded
    ep = lower_schedule(sched("splitk_summa", "round_robin", gk=3), mesh_sq)
    assert ep.mode == "splitk" and "grid_mismatch" in ep.reasons()


def test_gemm_workload_extraction():
    from repro.configs import smoke_config
    from repro.models.workload import gemm_workload
    cfg = smoke_config("olmo-1b")                   # d 64, ffn 160, vocab 256
    shapes = gemm_workload(cfg, batch=2, seq=8, kind="prefill")
    assert len(shapes) == len(set(shapes))          # deduped
    assert GEMMShape(16, 160, 64) in shapes         # FFN up at 16 tokens
    assert GEMMShape(16, 256, 64) in shapes         # LM head
    decode = gemm_workload(cfg, batch=2, seq=8, kind="decode")
    assert GEMMShape(2, 160, 64) in decode          # M = batch for decode


def test_planner_end_to_end_batch_then_rerequest():
    """ISSUE acceptance: batch-tune a workload, re-request the same shapes,
    and observe pure cache hits (zero enumeration on the second pass)."""
    from repro.configs import smoke_config
    from repro.models.workload import gemm_workload
    workload = gemm_workload(smoke_config("olmo-1b"), batch=4, seq=16,
                             kind="prefill")
    planner = make_planner()
    first = planner.batch_tune(workload)
    hits_before = planner.cache.stats.hits
    second = {s: planner.plan(s) for s in workload}
    assert planner.cache.stats.hits == hits_before + len(set(workload))
    for s in workload:
        assert second[s] is first[s]


def test_adapt_fits_inner_kernel_blocks_to_the_new_shape():
    """A transferred kernel's bn / bk that do not divide the new tile are
    cut to the largest LANE multiple that does (a padded B is a copy of
    it); edges that divide stay, and so does one no LANE multiple fits."""
    from repro.core.schedule import InnerKernel
    from repro.hw.config import tpu_pod_as_accelerator
    src = Schedule(GEMMShape(128, 2048, 2048), Tiling(1, 1, 1, tk=256),
                   "summa", elem_bytes=2,
                   inner_kernel=InnerKernel(128, 1024, 1024, dtype="bfloat16"))
    hw = tpu_pod_as_accelerator((1, 1))
    assert adapt(src, GEMMShape(128, 1536, 5120), hw).inner_kernel \
        .geometry() == (128, 768, 1024)
    assert adapt(src, GEMMShape(128, 24576, 1536), hw).inner_kernel \
        .geometry() == (128, 1024, 768)
    assert adapt(src, GEMMShape(128, 160, 2048), hw).inner_kernel \
        .geometry() == (128, 1024, 1024)
