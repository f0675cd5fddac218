"""Routing-path benchmark -> BENCH_routing.json (the perf trajectory of the
schedule->mesh lowering layer).

Four measurements on a model workload (smoke config, 16 fake CPU devices):

- **plan-resolve latency**: `Planner.plan_cached` per workload shape against
  a warmed cache (the trace-time dispatch cost every `pmm` callsite pays),
  plus `lower_schedule` per served plan (the ExecPlan resolution cost).
- **per-mode trace+lower wall time**: `jax.jit(dit_gemm).lower()` for every
  executed mode — auto baseline, summa, cannon, 1-D/3-D split-K, both
  reduction owners, both hierarchical compositions — the compile-side price
  of honoring the tuned dataflow instead of letting XLA place collectives.
- **fallback rate**: fraction of the workload's tuned plans that degrade to
  `auto` when lowered onto the mesh, with per-reason counts and the
  silent-degrade cross-check (must be 0: every degrade carries a reason).
- **per-mode execution efficiency vs XLA auto**: each executable mode
  (summa, cannon, splitk_summa, hierarchical, outer_systolic) runs the same
  GEMM set on a 4x4 host mesh, best-of-reps wall time against the `auto`
  baseline; `efficiency_vs_auto > 1` means the tuned collective pattern
  beat XLA's placement. This is the ground-truth signal the autotuner's
  simulator-side perf reports are validated against (on fake CPU devices
  the absolute numbers measure collective-schedule overhead, not real
  fabric bandwidth — see docs/benchmarking.md for the methodology and what
  a regression means).

Standalone (sets its own fake-device count; run before importing jax
elsewhere):

  PYTHONPATH=src python benchmarks/routing_bench.py --reps 1

Also exposed to benchmarks/run.py via a subprocess `run()` so the device
count does not leak into the other benchmarks' jax runtime.
"""
import argparse
import json
import os
import time
from typing import List


def _bench() -> dict:
    from repro.configs import smoke_config
    from repro.core.lower import lower_schedule, lowering_summary
    from repro.deploy import Planner
    from repro.hw.config import tpu_pod_as_accelerator
    from repro.launch.mesh import make_mesh
    from repro.models.workload import gemm_workload

    cfg = smoke_config("gemma-2b")
    hw = tpu_pod_as_accelerator((4, 4))
    planner = Planner(hw, max_candidates=8)
    workload = gemm_workload(cfg, batch=2, seq=16, kind="prefill")

    t0 = time.perf_counter()
    planner.batch_tune(workload)
    tune_us = (time.perf_counter() - t0) / len(workload) * 1e6

    t0 = time.perf_counter()
    plans = [planner.plan_cached(s) for s in workload]
    resolve_us = (time.perf_counter() - t0) / len(workload) * 1e6

    mesh = make_mesh((2, 4), ("data", "model"))
    t0 = time.perf_counter()
    eps = [lower_schedule(p.schedule, mesh, shape=s)
           for s, p in zip(workload, plans)]
    lower_us = (time.perf_counter() - t0) / len(workload) * 1e6
    summary = lowering_summary(eps)
    summary["fallback_rate"] = (summary["degraded"] / summary["total"]
                                if summary["total"] else 0.0)
    return {
        "workload_shapes": len(workload),
        "plan_cold_tune_us": round(tune_us, 1),
        "plan_resolve_us": round(resolve_us, 1),
        "lower_schedule_us": round(lower_us, 1),
        "workload_lowering": summary,
    }


def _bench_modes(reps: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.gemm import dit_gemm
    from repro.core.schedule import GEMMShape, Schedule, Tiling
    from repro.launch.mesh import make_mesh

    # 4x4: square, so `systolic` traces cannon and `systolic_over_summa`
    # traces the real outer_systolic mode instead of their fallbacks
    mesh = make_mesh((4, 4), ("data", "model"))
    rng = np.random.default_rng(0)
    M, N, K = 256, 256, 512
    a = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)

    cases = [("auto", None)]
    for df, gk, owner in (("summa", 1, "first"),
                          ("systolic", 1, "first"),
                          ("baseline", 1, "first"),
                          ("splitk_summa", 2, "round_robin"),
                          ("splitk_summa", 2, "first"),
                          ("splitk_summa", 16, "round_robin"),  # 1-D collapse
                          ("summa_over_systolic", 1, "first"),
                          ("systolic_over_summa", 1, "first")):
        sched = Schedule(GEMMShape(M, N, K), Tiling(2, 2, gk, tk=64), df,
                         reduce_owner=owner, inner=(2, 2))
        label = df if gk <= 2 else f"{df}_1d"
        if df == "splitk_summa" and gk == 2:
            label += f"_{owner}"
        cases.append((label, sched))

    out = {}
    for label, sched in cases:
        if sched is None:
            fn = jax.jit(lambda x, y: dit_gemm(x, y, mesh, mode="auto"))
        else:
            fn = jax.jit(lambda x, y, s=sched: dit_gemm(x, y, mesh, plan=s))
        best = float("inf")
        for _ in range(max(1, reps)):
            t0 = time.perf_counter()
            fn.lower(a, b)
            best = min(best, time.perf_counter() - t0)
        out[label] = round(best * 1e3, 2)
    return out


def _bench_efficiency(reps: int) -> dict:
    """Per-mode execution wall time vs XLA auto on a 4x4 host mesh.

    The 4x4 grid is the smallest square mesh on which EVERY executable mode
    — including the Fig. 6c outer-systolic composition (2x2 outer ring of
    2x2 inner groups) — lowers without fallback, so all modes run the same
    GEMM set. Every schedule's lowering is asserted clean before timing: a
    silent degrade would quietly benchmark `auto` against itself.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.gemm import dit_gemm
    from repro.core.lower import lower_schedule
    from repro.launch.mesh import make_mesh
    # the mode-case table, schedule construction, and timing discipline are
    # shared with the calibration harness (sim/calibrate.measure_modes) so
    # a new executable mode lands in both measured surfaces together
    from repro.sim.calibrate import (MODE_CASES, build_mode_schedule,
                                     time_best_of)

    mesh = make_mesh((4, 4), ("data", "model"))
    gemms = [(256, 256, 512), (512, 256, 1024)]
    rng = np.random.default_rng(0)

    auto_ms = []
    modes = {label: {"ms": [], "efficiency_vs_auto": []}
             for label, _, _ in MODE_CASES}
    for (M, N, K) in gemms:
        a = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((K, N)), jnp.float32)
        t_auto = time_best_of(jax.jit(
            lambda x, y: dit_gemm(x, y, mesh, mode="auto")), a, b, reps)
        auto_ms.append(round(t_auto * 1e3, 3))
        for label, df, kw in MODE_CASES:
            sched = build_mode_schedule(df, kw, 4, 4, (M, N, K))
            ep = lower_schedule(sched, mesh, shape=(M, N, K))
            if ep.mode != label or ep.degraded:
                raise RuntimeError(f"{df} lowered to {ep.describe()}, "
                                   f"expected clean {label}")
            t = time_best_of(jax.jit(
                lambda x, y, s=sched: dit_gemm(x, y, mesh, plan=s)), a, b,
                reps)
            modes[label]["ms"].append(round(t * 1e3, 3))
            modes[label]["efficiency_vs_auto"].append(round(t_auto / t, 3))
    for rec in modes.values():
        effs = rec["efficiency_vs_auto"]
        rec["geomean"] = round(
            float(np.exp(np.mean(np.log(np.asarray(effs))))), 3)
    return {
        "mesh": [4, 4],
        "gemms": [list(g) for g in gemms],
        "auto_ms": auto_ms,
        "modes": modes,
    }


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=3,
                    help="trace+lower / execution repetitions per mode "
                         "(best-of)")
    ap.add_argument("--skip-efficiency", action="store_true",
                    help="skip the per-mode execution timing (keep only the "
                         "trace-time measurements)")
    ap.add_argument("--out", default="BENCH_routing.json")
    args = ap.parse_args(argv)

    # must precede the first jax import (the lazy in-function imports below);
    # set here, not at module top, so merely importing this module (e.g.
    # from benchmarks/run.py) cannot leak fake devices into the host
    # process. Appended rather than set so a pre-existing XLA_FLAGS (dump
    # dirs etc.) keeps its settings alongside the fake-device count.
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=16").strip()
    result = _bench()
    result["trace_lower_ms"] = _bench_modes(args.reps)
    if not args.skip_efficiency:
        result["efficiency_vs_auto"] = _bench_efficiency(args.reps)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)

    wl = result["workload_lowering"]
    print(f"routing.plan_resolve,{result['plan_resolve_us']},"
          f"shapes={result['workload_shapes']} "
          f"cold={result['plan_cold_tune_us']}")
    print(f"routing.lower_schedule,{result['lower_schedule_us']},"
          f"fallback_rate={wl['fallback_rate']:.2f} "
          f"silent={wl['silent_auto_degrades']}")
    for label, ms in sorted(result["trace_lower_ms"].items()):
        print(f"routing.trace_lower.{label},{ms * 1e3:.1f},ms={ms}")
    for label, rec in sorted(result.get("efficiency_vs_auto",
                                        {}).get("modes", {}).items()):
        print(f"routing.exec.{label},{rec['ms'][0] * 1e3:.1f},"
              f"eff_vs_auto={rec['geomean']}")
    print(f"wrote {args.out}")
    return result


def run() -> List[str]:
    """benchmarks/run.py hook: subprocess so the fake-device XLA flag never
    leaks into the shared jax runtime of the other benchmarks."""
    import subprocess
    import sys
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--reps", "1",
         "--out", os.devnull],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH":
             os.pathsep.join(filter(None, [
                 os.path.join(os.path.dirname(__file__), "..", "src"),
                 os.environ.get("PYTHONPATH", "")]))})
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-500:])
    return [l for l in proc.stdout.splitlines() if l.startswith("routing.")]


if __name__ == "__main__":
    main()
