"""SLO serving benchmark under replayed traffic -> BENCH_serving.json.

The gate for the serving harness (`launch/traffic.py` + `deploy/batcher.py`)
— where the paper's per-GEMM wins are measured against *live traffic*
instead of one fixed batch. One seeded multi-tenant trace (two tenants,
two different model configs — gemma-2b + olmo-1b smoke — sharing one
planner, deliberately ragged odd prompt lengths) is replayed twice through
the virtual-clock continuous-batching loop against the pod-view planner:

- **bucket**: bucket-aware admission. Every batched GEMM M lands on the
  warmed pow-2 pool, so the replay is all plan-cache hits — zero cold
  shapes, zero virtual compile charges.
- **fifo**: the naive baseline. Admission fragments M into the long tail,
  and every fresh M pays the cold price (compile + bucketed transfer /
  online analytic tune) on the virtual clock.

Asserted bounds (the artifact's `bounds` section; `within_bounds` is the
single flag CI re-asserts):

- bucket goodput >= GOODPUT_FLOOR tokens/s (SLO-met tokens over makespan);
- bucket p99 total latency <= P99_BOUND_S;
- bucket plan-resolve rate >= RESOLVE_FLOOR (and fifo's too: raggedness
  must degrade latency, never correctness — bucketed transfers + the
  online analytic tuner still resolve every shape);
- bucket cold shapes == 0 (admission never leaves the warmed pool);
- bucket goodput >= fifo goodput on the SAME trace (the win is real).

  PYTHONPATH=src python benchmarks/serving_bench.py

Virtual-clock + cost-model arithmetic over GEMM workloads traced
abstractly from the model (no devices), fully deterministic.
docs/serving.md describes the traffic model; the artifact schema is in
docs/benchmarking.md.
"""
import argparse
import json
from typing import List

# Asserted bounds. Headroom note: at seed 7 the bucket run measures
# ~11k tok/s goodput with p99 ~51 ms and the fifo baseline collapses to ~0
# goodput (40 cold shapes' compile charges blow every deadline), so the
# floors below carry ~5x margin against cost-model recalibrations.
GOODPUT_FLOOR = 2000.0      # tokens/s, bucket run
P99_BOUND_S = 0.25          # total-latency p99, bucket run
RESOLVE_FLOOR = 1.0         # plan-resolve rate, BOTH runs
SEED = 7


def _traffic():
    from repro.launch.traffic import TenantSpec, TrafficConfig
    # odd, pow-2-straddling prompt lengths: exactly the ragged stream that
    # fragments naive admission (13+29=42 -> bucket 64; 47 -> 64; ...)
    return TrafficConfig(seed=SEED, tenants=(
        TenantSpec(name="gemma", arch="gemma-2b", rate_rps=200.0,
                   n_requests=24, prompt_lens=(13, 29, 47, 61),
                   gen_lens=(2, 3, 5)),
        TenantSpec(name="olmo", arch="olmo-1b", rate_rps=150.0,
                   n_requests=16, prompt_lens=(11, 23, 37),
                   gen_lens=(2, 4)),
    ))


def _replay(trace, tcfg, cfgs, mode: str, max_candidates: int) -> dict:
    from repro.deploy.batcher import BatchPolicy
    from repro.deploy.planner import Planner
    from repro.hw.config import tpu_pod_as_accelerator
    from repro.launch.traffic import serving_section, simulate, warm_pool
    policy = BatchPolicy(mode=mode)
    # a FRESH planner per mode: the fifo baseline must not inherit the
    # bucket run's online-tuned entries (or vice versa)
    planner = Planner(tpu_pod_as_accelerator((4, 4)),
                      max_candidates=max_candidates)
    warmed = warm_pool(planner, cfgs, policy, tcfg.max_rows(policy))
    result = simulate(trace, planner, cfgs, policy=policy,
                      precompiled=warmed)
    section = serving_section(result)
    section["warmed_pool"] = len(warmed)
    return section


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--plan-candidates", type=int, default=8,
                    help="autotuner width for the warm-up tunes (the "
                         "runtime knob)")
    ap.add_argument("--out", default="BENCH_serving.json")
    args = ap.parse_args(argv)

    from repro.configs import smoke_config
    from repro.launch.traffic import generate_trace

    tcfg = _traffic()
    trace = generate_trace(tcfg)
    cfgs = {t.name: smoke_config(t.arch) for t in tcfg.tenants}

    result = {"seed": tcfg.seed,
              "trace": {"requests": len(trace),
                        "tenants": [t.name for t in tcfg.tenants],
                        "archs": sorted({c.name for c in cfgs.values()})},
              "bounds": {"goodput_floor": GOODPUT_FLOOR,
                         "p99_bound_s": P99_BOUND_S,
                         "resolve_floor": RESOLVE_FLOOR,
                         "bucket_cold_shapes": 0},
              "runs": {}}
    for mode in ("bucket", "fifo"):
        section = _replay(trace, tcfg, cfgs, mode, args.plan_candidates)
        result["runs"][mode] = section
        print(f"serving.{mode},{section['p99_latency_s'] * 1e6:.1f},"
              f"goodput={section['goodput_tps']:.1f} "
              f"p99={section['p99_latency_s'] * 1e3:.1f}ms "
              f"miss={section['deadline_miss_rate']:.0%} "
              f"cold={section['cold_shapes']} "
              f"resolve={section['resolve_rate']:.3f} "
              f"util={section['mean_batch_utilization']:.2f}", flush=True)

    bucket, fifo = result["runs"]["bucket"], result["runs"]["fifo"]
    result["bucket_vs_fifo_goodput"] = (
        bucket["goodput_tps"] / fifo["goodput_tps"]
        if fifo["goodput_tps"] else float("inf"))
    violations = []
    if bucket["goodput_tps"] < GOODPUT_FLOOR:
        violations.append(f"bucket goodput_tps="
                          f"{bucket['goodput_tps']:.1f} < {GOODPUT_FLOOR}")
    if bucket["p99_latency_s"] > P99_BOUND_S:
        violations.append(f"bucket p99_latency_s="
                          f"{bucket['p99_latency_s']:.4f} > {P99_BOUND_S}")
    for mode in ("bucket", "fifo"):
        rate = result["runs"][mode]["resolve_rate"]
        if rate < RESOLVE_FLOOR:
            violations.append(f"{mode} resolve_rate={rate:.3f} "
                              f"< {RESOLVE_FLOOR}")
    if bucket["cold_shapes"] != 0:
        violations.append(f"bucket cold_shapes={bucket['cold_shapes']} "
                          f"!= 0 — admission left the warmed pool")
    if bucket["goodput_tps"] < fifo["goodput_tps"]:
        violations.append(f"bucket goodput {bucket['goodput_tps']:.1f} < "
                          f"fifo baseline {fifo['goodput_tps']:.1f}")
    result["within_bounds"] = not violations
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {args.out}")
    if violations:
        raise SystemExit("serving harness out of bounds: "
                         + "; ".join(violations))
    return result


def run() -> List[str]:
    """benchmarks/run.py hook — narrower warm-up tunes keep the CSV sweep
    fast; the standalone/CI invocation owns the full-width gate."""
    import contextlib
    import io
    import os
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            main(["--plan-candidates", "6", "--out", os.devnull])
    except SystemExit as e:
        # run.py's per-module handler catches Exception, not SystemExit
        raise RuntimeError(str(e))
    return [l for l in buf.getvalue().splitlines()
            if l.startswith("serving.")]


if __name__ == "__main__":
    main()
