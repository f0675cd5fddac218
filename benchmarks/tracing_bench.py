"""Tracer-overhead benchmark -> BENCH_tracing.json (the cost of the
observability layer on the dispatch path).

Three measurements, each with an asserted bound so CI fails when the
tracer stops being cheap:

- **span emit latency**: `Tracer.span` per call on the pure-python path
  (no jax) — the fixed cost every traced `pmm` dispatch and serve step
  pays. Bound: < 50us/span (measured ~1-2us).
- **maybe_span no-op latency**: `obs.trace.maybe_span` with NO tracer
  installed — the cost untraced production code pays at every
  instrumented callsite. Informational (ns-scale), no bound beyond the
  dispatch ratio below, which already covers it end-to-end.
- **routed dispatch overhead**: jit trace time of `pmm` through a warmed
  planner on the 4x4 host mesh (the same routed harness the routing
  benchmark uses), tracer installed vs not. The tracer adds span
  bookkeeping plus the provenance digests (`plan.digest()`,
  `calibration_digest`) that are only computed when tracing. Bound:
  traced/untraced ratio < 1.25 (jit tracing is ms-scale; span emission is
  us-scale).

The result JSON carries a `within_bounds` flag; the bench itself raises
when a bound is violated, so both standalone runs and CI catch a
regression without parsing the numbers.

Standalone (sets its own fake-device count; run before importing jax
elsewhere):

  PYTHONPATH=src python benchmarks/tracing_bench.py --reps 3

Also exposed to benchmarks/run.py via a subprocess `run()` so the device
count does not leak into the other benchmarks' jax runtime.
"""
import argparse
import json
import os
import time
from typing import List

SPAN_EMIT_BOUND_US = 50.0
DISPATCH_OVERHEAD_BOUND = 1.25


def _bench_span_emit(n: int = 20_000) -> dict:
    """Pure-python span emission cost (no jax in the loop)."""
    from repro.obs import Tracer, set_tracer
    from repro.obs.trace import maybe_span

    tracer = Tracer(process_name="bench", max_events=n + 10)
    t0 = time.perf_counter()
    for i in range(n):
        with tracer.span("bench.span", tag="t", i=i):
            pass
    span_us = (time.perf_counter() - t0) / n * 1e6

    set_tracer(None)
    t0 = time.perf_counter()
    for i in range(n):
        with maybe_span("bench.noop", i=i):
            pass
    noop_ns = (time.perf_counter() - t0) / n * 1e9
    return {"span_emit_us": round(span_us, 3),
            "maybe_span_noop_ns": round(noop_ns, 1),
            "n": n}


def _bench_dispatch(reps: int) -> dict:
    """jit trace time of routed `pmm` with vs without a tracer installed.

    Fresh `jax.jit` wrappers per repetition keep every trace cold — a
    cached trace would measure dict lookup, not the dispatch path."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import smoke_config
    from repro.deploy import Planner
    from repro.hw.config import tpu_pod_as_accelerator
    from repro.launch.mesh import make_mesh
    from repro.models import shard_ctx
    from repro.models.matmul import pmm
    from repro.models.workload import gemm_workload
    from repro.obs import Tracer, set_tracer

    cfg = smoke_config("gemma-2b")
    hw = tpu_pod_as_accelerator((4, 4))
    planner = Planner(hw, max_candidates=8)
    workload = gemm_workload(cfg, batch=2, seq=16, kind="prefill")
    planner.batch_tune(workload)

    mesh = make_mesh((4, 4), ("data", "model"))
    ctx = shard_ctx.GemmContext(mesh=mesh, planner=planner)
    rng = np.random.default_rng(0)
    args = [(jnp.asarray(rng.standard_normal((s.m, s.k)), jnp.float32),
             jnp.asarray(rng.standard_normal((s.k, s.n)), jnp.float32))
            for s in dict.fromkeys(workload)]

    def trace_workload() -> float:
        t0 = time.perf_counter()
        with shard_ctx.gemm_context(ctx):
            for i, (a, b) in enumerate(args):
                fn = jax.jit(lambda x, w, t=f"bench.{i}": pmm(x, w, tag=t))
                fn.lower(a, b)
        return (time.perf_counter() - t0) / len(args) * 1e6

    # warm once (first trace pays jax setup costs neither side should own)
    trace_workload()

    untraced = traced = float("inf")
    for _ in range(max(1, reps)):
        set_tracer(None)
        untraced = min(untraced, trace_workload())
        set_tracer(Tracer(process_name="bench"))
        traced = min(traced, trace_workload())
    set_tracer(None)
    return {"workload_shapes": len(args),
            "untraced_dispatch_us": round(untraced, 1),
            "traced_dispatch_us": round(traced, 1),
            "overhead_ratio": round(traced / untraced, 3)}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=3,
                    help="dispatch-trace repetitions (best-of)")
    ap.add_argument("--out", default="BENCH_tracing.json")
    args = ap.parse_args(argv)

    # must precede the first jax import (the lazy in-function imports
    # above); appended rather than set so a pre-existing XLA_FLAGS keeps
    # its settings alongside the fake-device count.
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=16").strip()

    result = _bench_span_emit()
    result.update(_bench_dispatch(args.reps))
    result["bounds"] = {"span_emit_us": SPAN_EMIT_BOUND_US,
                       "overhead_ratio": DISPATCH_OVERHEAD_BOUND}
    result["within_bounds"] = (
        result["span_emit_us"] < SPAN_EMIT_BOUND_US
        and result["overhead_ratio"] < DISPATCH_OVERHEAD_BOUND)
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)

    print(f"tracing.span_emit,{result['span_emit_us']},"
          f"noop_ns={result['maybe_span_noop_ns']}")
    print(f"tracing.dispatch,{result['traced_dispatch_us']},"
          f"untraced={result['untraced_dispatch_us']} "
          f"ratio={result['overhead_ratio']}")
    print(f"wrote {args.out}")
    if not result["within_bounds"]:
        raise SystemExit(
            f"tracing overhead out of bounds: "
            f"span_emit_us={result['span_emit_us']} "
            f"(< {SPAN_EMIT_BOUND_US}), "
            f"overhead_ratio={result['overhead_ratio']} "
            f"(< {DISPATCH_OVERHEAD_BOUND})")
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
    return [l for l in proc.stdout.splitlines() if l.startswith("tracing.")]


if __name__ == "__main__":
    main()
