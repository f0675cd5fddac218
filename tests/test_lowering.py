"""Schedule->mesh lowering (repro.core.lower) — the ExecPlan contract.

Two halves:

1. **Resolution + fallback reasons** (no devices): `lower_schedule` only
   needs `mesh.shape`, so every branch of the lowering — each DATAFLOWS
   name, each mesh-view construction, and each machine-readable fallback
   reason — is pinned with bare namespace meshes. The two hierarchical
   compositions resolve to DISTINCT modes (Fig. 6d -> `hierarchical`,
   Fig. 6c -> `outer_systolic`), with the Fig. 6c ring legality
   (square outer grid of >= 2) pinned branch by branch.
2. **Execution parity** (slow, subprocess with fake devices): every resolved
   mode — including the nested 3-D `splitk_summa`, the `hierarchical`
   outer-SUMMA-over-inner-Cannon mode, and the `outer_systolic` outer
   Cannon ring of inner SUMMA groups — matches the `auto` baseline
   numerically on 2x2 / 2x4 / 4x4 meshes, the tuned gk>1 grid executes
   true 3-D split-K on an 8-device mesh (the ROADMAP acceptance), and the
   new modes are reverse-differentiable. A separate subprocess proves a
   Fig. 6c-tuned schedule reaches `outer_systolic` through the `pmm`
   routed-dispatch path.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import pytest

from repro.core import lower
from repro.core.lower import (ExecPlan, Fallback, MeshView, lower_schedule,
                              lowering_summary)
from repro.core.schedule import (DATAFLOWS, INNER_VMEM_BUDGET, GEMMShape,
                                 InnerKernel, Schedule, Tiling)


def mesh2(dm, dn):
    return SimpleNamespace(shape={"data": dm, "model": dn},
                           axis_names=("data", "model"))


def sched(df, m=64, n=64, k=128, gm=2, gn=2, gk=1, owner="first",
          inner=(2, 2)):
    return Schedule(GEMMShape(m, n, k), Tiling(gm, gn, gk, tk=32), df,
                    reduce_owner=owner, inner=inner)


# ---------------------------------------------------------------------------
# every dataflow name has an explicit lowering
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("df", DATAFLOWS)
@pytest.mark.parametrize("mesh", [mesh2(2, 2), mesh2(2, 4), mesh2(4, 4)],
                         ids=["2x2", "2x4", "4x4"])
def test_every_dataflow_lowers(df, mesh):
    """Regression for the silent default branch: every name in DATAFLOWS —
    including both hierarchical compositions — resolves without error and
    lands on a known mode."""
    ep = lower_schedule(sched(df, gk=2 if df == "splitk_summa" else 1), mesh)
    assert isinstance(ep, ExecPlan)
    assert ep.mode in lower.EXEC_MODES
    assert ep.requested == df
    # the Fig. 6d composition gets the hierarchical mode, never a summa
    # collapse; Fig. 6c gets outer_systolic where the outer ring fits
    # (square outer grid >= 2, i.e. the 4x4 mesh) and hierarchical elsewhere
    if df == "summa_over_systolic":
        assert ep.mode == "hierarchical"
        assert ep.axes["inner_row"] == "data_in"
    if df == "systolic_over_summa":
        dm, dn = mesh.shape["data"], mesh.shape["model"]
        want = "outer_systolic" if (dm == dn and dm // 2 >= 2) \
            else "hierarchical"
        assert ep.mode == want
        assert ep.axes["inner_row"] == "data_in"
    if df == "splitk_summa":
        assert ep.mode == "splitk_summa"


def test_unknown_dataflow_reason():
    ep = lower_schedule(sched("warp_drive"), mesh2(2, 2))
    assert ep.mode == "summa"
    assert ep.reasons() == (lower.UNKNOWN_DATAFLOW,)


# ---------------------------------------------------------------------------
# mesh-view construction: the tuned grid survives to execution
# ---------------------------------------------------------------------------

def test_splitk_view_factors_col_axis():
    ep = lower_schedule(sched("splitk_summa", gk=2, owner="round_robin"),
                        mesh2(2, 4))
    assert ep.mode == "splitk_summa" and not ep.fallbacks
    assert ep.kwargs["scatter"] is True
    sizes = ep.view.axis_sizes(mesh2(2, 4))
    assert sizes == {"data": 2, "model": 2, "splitk": 2}


def test_splitk_view_factors_row_axis():
    # gk does not divide the 1-wide column axis; it factors out of the rows
    ep = lower_schedule(sched("splitk_summa", gk=2), mesh2(4, 1))
    assert ep.mode == "splitk_summa" and not ep.fallbacks
    assert ep.view.axis_sizes(mesh2(4, 1)) == {"data": 2, "splitk": 2,
                                               "model": 1}


def test_splitk_grid_mismatch_collapses_to_1d():
    ep = lower_schedule(sched("splitk_summa", gk=3), mesh2(2, 4))
    assert ep.mode == "splitk"
    assert ep.reasons() == (lower.GRID_MISMATCH,)
    assert ep.axes["k"] == "model" and ep.view is None
    assert not ep.degraded          # 1-D split-K still honors the dataflow


def test_splitk_gk_one_is_summa():
    ep = lower_schedule(sched("splitk_summa", gk=1), mesh2(2, 2))
    assert ep.mode == "summa"
    assert ep.reasons() == (lower.GK_IS_ONE,)


def test_hierarchical_view():
    ep = lower_schedule(sched("summa_over_systolic", inner=(2, 2)),
                        mesh2(2, 4))
    assert ep.mode == "hierarchical" and not ep.fallbacks
    assert ep.view.axis_sizes(mesh2(2, 4)) == {
        "data": 1, "data_in": 2, "model": 2, "model_in": 2}
    assert ep.kwargs["inner"] == (2, 2)


def test_outer_systolic_view():
    """Fig. 6c resolves to its own mode on a square outer grid — same
    4-axis view as hierarchical, distinct collective program."""
    ep = lower_schedule(sched("systolic_over_summa", inner=(2, 2)),
                        mesh2(4, 4))
    assert ep.mode == "outer_systolic" and not ep.fallbacks
    assert ep.view.axis_sizes(mesh2(4, 4)) == {
        "data": 2, "data_in": 2, "model": 2, "model_in": 2}
    assert ep.kwargs["inner"] == (2, 2)
    assert ep.axes["inner_row"] == "data_in"
    assert ep.axes["inner_col"] == "model_in"


def test_outer_systolic_production_mesh():
    """The 16x16 production grid: an 8x8 outer ring of 2x2 SUMMA groups."""
    ep = lower_schedule(sched("systolic_over_summa", m=256, n=256, k=2048),
                        mesh2(16, 16))
    assert ep.mode == "outer_systolic" and not ep.fallbacks
    assert ep.view.axis_sizes(mesh2(16, 16)) == {
        "data": 8, "data_in": 2, "model": 8, "model_in": 2}


def test_view_materialize_preserves_extra_axes():
    """A multi-pod mesh's pod axis passes through the view untouched."""
    view = MeshView(splits=(("model", (("model", 2), ("splitk", 2))),))
    pod_mesh = SimpleNamespace(shape={"pod": 2, "data": 2, "model": 4},
                               axis_names=("pod", "data", "model"))
    assert view.axis_sizes(pod_mesh) == {"pod": 2, "data": 2, "model": 2,
                                         "splitk": 2}


# ---------------------------------------------------------------------------
# fallback reasons, branch by branch
# ---------------------------------------------------------------------------

def test_non_square_systolic():
    ep = lower_schedule(sched("systolic"), mesh2(2, 4))
    assert ep.mode == "summa"
    assert ep.reasons() == (lower.NON_SQUARE_SYSTOLIC,)
    assert not ep.degraded


def test_non_square_inner():
    ep = lower_schedule(sched("summa_over_systolic", inner=(1, 2)),
                        mesh2(2, 4))
    assert ep.mode == "summa"
    assert ep.reasons() == (lower.NON_SQUARE_INNER,)


def test_inner_grid_mismatch():
    ep = lower_schedule(sched("systolic_over_summa", inner=(3, 3)),
                        mesh2(4, 4))
    assert ep.mode == "summa"
    assert ep.reasons() == (lower.INNER_GRID_MISMATCH,)


def test_non_square_outer_falls_to_hierarchical():
    """Fig. 6c's ring needs a square outer grid; a rectangular one still
    executes the hierarchical (Fig. 6d-shaped) composition, not summa."""
    ep = lower_schedule(sched("systolic_over_summa", inner=(2, 2)),
                        mesh2(4, 8))
    assert ep.mode == "hierarchical"
    assert ep.reasons() == (lower.NON_SQUARE_OUTER,)
    assert ep.fallbacks[0].from_mode == "outer_systolic"
    assert not ep.degraded
    # the 4-axis view survives the fallback — hierarchical runs on it
    assert ep.view.axis_sizes(mesh2(4, 8)) == {
        "data": 2, "data_in": 2, "model": 4, "model_in": 2}


def test_outer_ring_too_small_falls_to_hierarchical():
    """A 1x1 outer grid has no ring to rotate chunks around."""
    ep = lower_schedule(sched("systolic_over_summa", inner=(2, 2)),
                        mesh2(2, 2))
    assert ep.mode == "hierarchical"
    assert ep.reasons() == (lower.OUTER_RING_TOO_SMALL,)
    assert not ep.degraded


def test_outer_systolic_k_indivisible_degrades_to_auto():
    # the ring fits (2x2 outer of 2x2 inner), but K=132 % (Om*ih^2)=8 != 0
    ep = lower_schedule(sched("systolic_over_summa", k=132), mesh2(4, 4))
    assert ep.mode == "auto" and ep.degraded
    assert ep.reasons() == (lower.K_NOT_DIVISIBLE,)
    assert ep.fallbacks[0].from_mode == "outer_systolic"


def test_outer_systolic_non_square_inner_reports_wanted_mode():
    """A non-square inner group on the Fig. 6c composition records the
    fallback as coming FROM outer_systolic (what the schedule asked for)."""
    ep = lower_schedule(sched("systolic_over_summa", inner=(1, 2)),
                        mesh2(4, 4))
    assert ep.mode == "summa"
    assert ep.reasons() == (lower.NON_SQUARE_INNER,)
    assert ep.fallbacks[0].from_mode == "outer_systolic"


@pytest.mark.parametrize("df,shape,reason", [
    ("summa", (63, 64, 128), lower.M_NOT_DIVISIBLE),
    ("summa", (64, 63, 128), lower.N_NOT_DIVISIBLE),
    ("summa", (64, 64, 130), lower.K_NOT_DIVISIBLE),
    ("systolic", (64, 64, 127), lower.K_NOT_DIVISIBLE),
    ("baseline", (63, 64, 128), lower.M_NOT_DIVISIBLE),
    ("baseline", (64, 64, 127), lower.K_NOT_DIVISIBLE),
])
def test_indivisible_degrades_to_auto(df, shape, reason):
    m, n, k = shape
    ep = lower_schedule(sched(df, m=m, n=n, k=k), mesh2(2, 2))
    assert ep.mode == "auto" and ep.degraded
    assert ep.fallbacks[-1] == Fallback(reason, ep.fallbacks[-1].from_mode,
                                        "auto")


def test_splitk_3d_k_indivisible_degrades_to_auto():
    # gk=2 fits the mesh, but K=130 % (gk*rm*rn)=8 != 0
    ep = lower_schedule(sched("splitk_summa", gk=2, k=130), mesh2(2, 4))
    assert ep.mode == "auto"
    assert ep.reasons() == (lower.K_NOT_DIVISIBLE,)


def test_splitk_scatter_demotes_not_degrades():
    # round_robin wants psum_scatter, but M=2 < rm*gk=4: the reduction
    # demotes to the replicated-C psum ('first' analogue), mode unchanged
    ep = lower_schedule(sched("splitk_summa", gk=2, m=2, owner="round_robin"),
                        mesh2(2, 4))
    assert ep.mode == "splitk_summa"
    assert ep.kwargs["scatter"] is False
    assert lower.SCATTER_M_INDIVISIBLE in ep.reasons()
    assert not ep.degraded


def test_splitk_1d_scatter_demotion():
    # grid mismatch -> 1-D splitk over the 4-wide model axis; M=2 % 4 != 0
    # demotes scatter there too (the old inline dit_gemm check, now in one
    # place so dispatch and validation cannot drift)
    ep = lower_schedule(sched("splitk_summa", gk=3, m=2, owner="round_robin"),
                        mesh2(2, 4))
    assert ep.mode == "splitk" and ep.kwargs["scatter"] is False
    assert ep.reasons() == (lower.GRID_MISMATCH, lower.SCATTER_M_INDIVISIBLE)


def test_fallback_chain_hierarchical_to_auto():
    # inner group fits, but K % (Om*On*ih) fails -> hierarchical -> auto
    ep = lower_schedule(sched("summa_over_systolic", inner=(2, 2), k=126),
                        mesh2(2, 4))
    assert ep.mode == "auto"
    assert ep.reasons() == (lower.K_NOT_DIVISIBLE,)
    assert ep.fallbacks[0].from_mode == "hierarchical"


def test_overrides_validated_before_dispatch():
    """Caller kwargs merge BEFORE legality: forcing scatter on an
    M-indivisible problem is demoted, not crashed."""
    ep = lower_schedule(sched("splitk_summa", gk=2, m=2, owner="first"),
                        mesh2(2, 4), overrides={"scatter": True})
    assert ep.kwargs["scatter"] is False
    assert lower.SCATTER_M_INDIVISIBLE in ep.reasons()


def test_shape_override_beats_schedule_shape():
    """Bucketed serving dispatches neighbour shapes: legality must check the
    actual operands, not the tuned shape."""
    tuned = sched("summa", m=64, n=64, k=128)
    ok = lower_schedule(tuned, mesh2(2, 2))
    assert ok.mode == "summa"
    served = lower_schedule(tuned, mesh2(2, 2), shape=(64, 64, 130))
    assert served.mode == "auto"
    assert lower.K_NOT_DIVISIBLE in served.reasons()


def test_lowering_summary_counts():
    mesh = mesh2(2, 4)
    plans = [lower_schedule(sched("summa"), mesh),
             lower_schedule(sched("systolic"), mesh),
             lower_schedule(sched("summa", k=130), mesh)]
    s = lowering_summary(plans)
    assert s["modes"] == {"summa": 2, "auto": 1}
    assert s["degrade_reasons"] == {lower.NON_SQUARE_SYSTOLIC: 1,
                                    lower.K_NOT_DIVISIBLE: 1}
    assert s["degraded"] == 1 and s["silent_auto_degrades"] == 0
    assert s["total"] == 3


def test_describe_is_informative():
    ep = lower_schedule(sched("systolic"), mesh2(2, 4))
    text = ep.describe()
    assert "systolic" in text and "summa" in text
    assert lower.NON_SQUARE_SYSTOLIC in text


# ---------------------------------------------------------------------------
# two-level fields: InnerKernel / overlap through the lowering
# ---------------------------------------------------------------------------

def test_inner_kernel_and_overlap_carried_through():
    ik = InnerKernel(64, 64, 32, dtype="float32")
    s = dataclasses.replace(sched("summa"), inner_kernel=ik, overlap=True)
    ep = lower_schedule(s, mesh2(2, 2))
    assert ep.mode == "summa" and not ep.fallbacks
    assert ep.inner_kernel == ik and ep.overlap is True
    assert "ik=" in ep.describe() and "overlap" in ep.describe()
    d = ep.to_dict()
    assert d["inner_kernel"] == ik.to_dict() and d["overlap"] is True


def test_oversized_inner_kernel_demotes_not_degrades():
    """A kernel whose working set busts the VMEM budget drops to the
    XLA-picked local GEMM with a recorded reason — mode unchanged (the
    scatter_m_indivisible idiom)."""
    big = InnerKernel(2048, 2048, 2048, dtype="float32")
    assert big.working_set_bytes() > INNER_VMEM_BUDGET
    ep = lower_schedule(dataclasses.replace(sched("summa"), inner_kernel=big),
                        mesh2(2, 2))
    assert ep.mode == "summa" and not ep.degraded
    assert ep.inner_kernel is None
    assert ep.reasons() == (lower.INNER_KERNEL_TOO_LARGE,)


def test_unaligned_inner_kernel_demotes_not_degrades():
    """The olmo-1b LM-head decode block a 4x4 pod view once planned
    (8x3144x512: 3144 is no multiple of 128 and does not span the 50304
    local columns) is refused by the TPU compiler, so lowering on one chip
    drops it to the XLA-picked local GEMM with a recorded reason."""
    bad = InnerKernel(8, 3144, 512, dtype="bfloat16")
    s = Schedule(GEMMShape(8, 50304, 2048), Tiling(1, 16, 1, tk=512),
                 "summa", inner_kernel=bad)
    ep = lower_schedule(s, mesh2(1, 1))
    assert ep.mode == "summa" and not ep.degraded
    assert ep.inner_kernel is None
    assert ep.reasons() == (lower.INNER_KERNEL_UNALIGNED,)


@pytest.mark.parametrize("block,local,ok", [
    ((8, 128, 512), (8, 50304, 2048), True),     # on the (8, 128) tiling
    ((8, 3144, 512), (8, 50304, 2048), False),   # bn off the lane tiling
    ((4, 128, 128), (8, 128, 128), False),       # bm off the sublane tiling
    ((32, 32, 32), (32, 32, 32), True),          # blocks span the local dims
    ((64, 64, 32), (32, 32, 32), True),          # ... after padding up
])
def test_kernel_tiles(block, local, ok):
    assert lower.kernel_tiles(InnerKernel(*block), local) is ok


def test_tpu_inner_candidates_sit_on_the_tiling():
    """Every closed-form candidate for olmo-1b's GEMMs on a v5e pod view
    is a block the TPU compiler accepts on the tile it was priced for."""
    from repro.core.schedule import inner_kernel_candidates
    from repro.hw.config import tpu_pod_as_accelerator
    hw = tpu_pod_as_accelerator((4, 4))
    for m, n, k in ((8, 50304, 2048), (8, 2048, 8192), (2048, 8192, 2048)):
        for gm, gn in ((1, 16), (4, 4), (16, 1)):
            s = Schedule(GEMMShape(m, n, k), Tiling(gm, gn, 1, tk=512),
                         "summa", elem_bytes=2)
            tm, tn, kl = s.tiling.tile_dims(s.shape)
            for ik in inner_kernel_candidates(s, hw):
                assert lower.kernel_tiles(ik, (tm, tn, min(512, kl))), (
                    (m, n, k), (gm, gn), ik.describe())


def test_auto_landing_sheds_inner_level():
    """A degrade to auto drops kernel and overlap without an extra reason
    — auto has no mode body to honor them, and the degrade itself is
    already recorded."""
    ik = InnerKernel(64, 64, 32, dtype="float32")
    s = dataclasses.replace(sched("summa", k=130), inner_kernel=ik,
                            overlap=True)
    ep = lower_schedule(s, mesh2(2, 2))
    assert ep.mode == "auto" and ep.degraded
    assert ep.inner_kernel is None and ep.overlap is False
    assert ep.reasons() == (lower.K_NOT_DIVISIBLE,)


# ---------------------------------------------------------------------------
# execution parity vs auto (multi-device subprocess)
# ---------------------------------------------------------------------------

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

PARITY_BODY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.gemm import dit_gemm
    from repro.core.lower import lower_schedule
    from repro.core.schedule import GEMMShape, Schedule, Tiling
    from repro.launch.mesh import make_mesh

    rng = np.random.default_rng(0)
    M, N, K = 64, 96, 128
    a = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
    ref = np.asarray(a @ b)

    def run(mesh, sched):
        ep = lower_schedule(sched, mesh, "data", "model", shape=(M, N, K))
        out = np.asarray(jax.jit(
            lambda x, y: dit_gemm(x, y, mesh, plan=sched))(a, b))
        auto = np.asarray(jax.jit(
            lambda x, y: dit_gemm(x, y, mesh, mode="auto"))(a, b))
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(out, auto, rtol=1e-4, atol=1e-4)
        return ep

    CASES = [
        ("summa", dict()),
        ("systolic", dict()),
        ("baseline", dict()),
        ("splitk_summa", dict(gk=2, owner="round_robin")),
        ("splitk_summa", dict(gk=2, owner="first")),
        ("splitk_summa", dict(gk=8, owner="round_robin")),  # 1-D collapse
        ("systolic_over_summa", dict()),
        ("summa_over_systolic", dict()),
    ]
    for mesh_shape in ((2, 2), (2, 4), (4, 4)):
        mesh = make_mesh(mesh_shape, ("data", "model"))
        for df, kw in CASES:
            sched = Schedule(GEMMShape(M, N, K),
                             Tiling(2, 2, kw.get("gk", 1), tk=32), df,
                             reduce_owner=kw.get("owner", "first"),
                             inner=(2, 2))
            ep = run(mesh, sched)
            assert not ep.degraded, (mesh_shape, df, ep.describe())
            # Fig. 6c runs its OWN mode where the outer ring fits
            if df == "systolic_over_summa" and mesh_shape == (4, 4):
                assert ep.mode == "outer_systolic", ep.describe()
            print("OK", mesh_shape, df, "->", ep.mode)

    # ROADMAP acceptance: a tuned gk>1 schedule executes TRUE 3-D split-K
    # on the 8-device mesh (not the 1-D collapse), matching auto
    mesh8 = make_mesh((2, 4), ("data", "model"))
    s3d = Schedule(GEMMShape(M, N, K), Tiling(2, 2, 2, tk=32),
                   "splitk_summa", reduce_owner="round_robin")
    ep = lower_schedule(s3d, mesh8, "data", "model", shape=(M, N, K))
    assert ep.mode == "splitk_summa" and not ep.fallbacks, ep.describe()
    assert ep.view.axis_sizes(mesh8) == {"data": 2, "model": 2, "splitk": 2}
    run(mesh8, s3d)
    print("OK 3-D splitk on 8 devices")

    # outer-systolic with degenerate (1, 1) inner groups IS outer Cannon:
    # the smallest mesh that exercises the group-level ring (2x2 outer)
    mesh4 = make_mesh((2, 2), ("data", "model"))
    s6c_min = Schedule(GEMMShape(M, N, K), Tiling(2, 2, 1, tk=32),
                       "systolic_over_summa", inner=(1, 1))
    ep = lower_schedule(s6c_min, mesh4, "data", "model", shape=(M, N, K))
    assert ep.mode == "outer_systolic" and not ep.fallbacks, ep.describe()
    run(mesh4, s6c_min)
    print("OK outer_systolic (1x1 inner) on 2x2")

    # the new modes are reverse-differentiable (routed training)
    ones = jnp.ones((M, N), jnp.float32)
    mesh16 = make_mesh((4, 4), ("data", "model"))
    for df, gk, mesh in (("splitk_summa", 2, mesh8),
                         ("summa_over_systolic", 1, mesh8),
                         ("systolic_over_summa", 1, mesh16),
                         ("systolic_over_summa", 1, mesh4)):
        sched = Schedule(GEMMShape(M, N, K), Tiling(2, 2, gk, tk=32), df,
                         reduce_owner="round_robin",
                         inner=(1, 1) if mesh is mesh4 else (2, 2))
        ep = lower_schedule(sched, mesh, "data", "model", shape=(M, N, K))
        ga, gb = jax.grad(
            lambda x, y, s=sched, m=mesh: dit_gemm(x, y, m, plan=s).sum(),
            argnums=(0, 1))(a, b)
        np.testing.assert_allclose(np.asarray(ga), np.asarray(ones @ b.T),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(gb), np.asarray(a.T @ ones),
                                   rtol=1e-4, atol=1e-4)
        print("OK grad", df, "->", ep.mode)
    print("ALL_OK")
""")


@pytest.mark.slow
def test_exec_parity_multidevice():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", PARITY_BODY], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (f"stdout:\n{proc.stdout}\n"
                                  f"stderr:\n{proc.stderr}")
    assert "ALL_OK" in proc.stdout


ROUTED_6C_BODY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.schedule import GEMMShape
    from repro.deploy import Planner
    from repro.hw.config import tpu_pod_as_accelerator
    from repro.launch.mesh import make_mesh
    from repro.models import shard_ctx
    from repro.models.matmul import pmm
    from repro.models.shard_ctx import GemmContext

    # a REAL Fig. 6c tune: the restricted search must enumerate and price
    # systolic_over_summa candidates (autotuner hierarchical enumeration)
    planner = Planner(tpu_pod_as_accelerator((4, 4)), elem_bytes=4,
                      max_candidates=12, dataflows=["systolic_over_summa"])
    shape = GEMMShape(256, 256, 512)
    plans = planner.batch_tune([shape])
    assert plans[shape].schedule.dataflow == "systolic_over_summa"

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 128, 512)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((512, 256)), jnp.float32)
    mesh = make_mesh((4, 4), ("data", "model"))
    ctx = GemmContext(mesh=mesh, planner=planner)
    with shard_ctx.gemm_context(ctx):
        routed = jax.jit(lambda x, w: pmm(x, w, tag="fig6c"))(x, w)

    # the tuned composition survives pmm -> lower_schedule -> dit_gemm:
    # the stats record the executed mode, with no degrade of any kind
    assert ctx.stats.hits == 1, ctx.stats.describe()
    assert ctx.stats.modes == {"outer_systolic": 1}, ctx.stats.describe()
    assert not ctx.stats.degrades and ctx.stats.silent_degrades == 0
    np.testing.assert_allclose(np.asarray(routed), np.asarray(x @ w),
                               rtol=1e-3, atol=1e-3)
    print("stats:", ctx.stats.describe())
    print("ALL_OK")
""")


@pytest.mark.slow
def test_fig6c_tuned_schedule_routes_to_outer_systolic():
    """End to end: a schedule tuned under the Fig. 6c restriction reaches
    the `outer_systolic` mode through the pmm routed-dispatch path, and the
    context stats record it (the mode histogram launchers report)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", ROUTED_6C_BODY], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (f"stdout:\n{proc.stdout}\n"
                                  f"stderr:\n{proc.stderr}")
    assert "ALL_OK" in proc.stdout


@pytest.mark.parametrize("m, n, k, want", [
    (16, 3072, 3072, (16, 1024, 1024)),      # phi4-mini's decode q / o
    (16, 3072, 8192, (16, 1024, 1024)),
    (128, 24576, 1536, (128, 1024, 768)),    # DeepSeek-V2's q_up, 1536 deep
    (128, 512, 5120, (128, 512, 1024)),
    (2048, 3072, 3072, None),                # a prefill chunk: not streamed
], ids=lambda v: str(v))
def test_decode_tiles_stream_the_largest_b_block(m, n, k, want):
    """A tile of at most STREAM_ROWS rows puts first the double-buffered
    kernel with the largest B block whose edges divide its N and K, and it
    prices exactly like the planner's K-chunk kernel, so it wins the tie."""
    from repro.core.schedule import (STREAM_ROWS, build_program,
                                     inner_kernel_candidates)
    from repro.hw.config import tpu_pod_as_accelerator
    from repro.sim.perf import estimate_sweep
    hw = tpu_pod_as_accelerator((1, 1))
    s = Schedule(GEMMShape(m, n, k), Tiling(1, 1, 1, tk=256), "summa",
                 elem_bytes=2)
    cands = inner_kernel_candidates(s, hw)
    if want is None:
        assert m > STREAM_ROWS and cands[0].bk <= 256
        return
    assert cands[0].geometry() == want and cands[0].depth == 2
    prog = build_program(s, hw)
    (_, first), (_, chunked) = estimate_sweep(prog, hw, cands[:2])
    assert first.total_time == chunked.total_time
