#!/usr/bin/env python3
"""Smoke proof that the routed serve path runs on a TPU, in one process.

    python chip_smoke.py               # one chip: phases (a)-(d)
    python chip_smoke.py --four-chips  # four chips: the 2x2 mesh path only

One chip, olmo-1b at its published width with random weights from --seed:

  (a) device check: JAX must see a TPU, or the script exits non-zero;
  (b) `local_matmul` at olmo-1b's decode (batch 8) and prefill (batch 8 x
      256 tokens) GEMM shapes under the planner's inner kernels, against
      an fp32 `jnp.dot`;
  (c) a routed serve run (batch 8, prompt 64, gen 16) through
      `repro.launch.serve`'s own functions, planned into a plan cache this
      script creates fresh; its compiled step must hold the Pallas kernel
      (`tpu_custom_call`), so no GEMM went through the interpreter or a
      plain `jnp.dot`;
  (d) the run report shows no silent degrade and a plan-resolve rate of 1,
      and the first decode step's logits agree with the unrouted program
      (`pmm` is plain `x @ w`) on the same cache.

`--four-chips` runs only the mesh path and what it is compared with:
`dit_gemm` in summa / cannon / splitk_summa / allgather / auto on a 2x2
mesh against `jnp.dot`, and routed olmo-1b decode steps on that mesh
against the unrouted logits. Every output must span all four devices.

Earlier lines name the device, each phase and its set-up (compile) time;
the serve line's tok/s is informational only. The last line of stdout is
one JSON object naming the device, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

from repro.configs import get_config
from repro.core.gemm import dit_gemm
from repro.core.lower import lower_schedule
from repro.core.schedule import (GEMMShape, Schedule, Tiling,
                                 inner_kernel_candidates)
from repro.deploy.warmup import build_planner, warm_buckets
from repro.hw.config import tpu_pod_as_accelerator
from repro.kernels.ops import local_matmul
from repro.launch import serve
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import host_mesh_grid, make_host_mesh, make_mesh
from repro.models import shard_ctx
from repro.models.model import decode_init, init_params
from repro.models.workload import gemm_workload
from repro.obs import Tracer, render_run_report, set_tracer
from repro.train.steps import make_serve_step

ARCH = "olmo-1b"
BATCH, PROMPT, GEN = 8, 64, 16
DECODE_MAX_LEN, PREFILL_LEN = 2048, 256
PLAN_CACHE = ROOT / "results" / "chip_smoke" / "plan_cache"
# bf16 operands, fp32 accumulation: one GEMM is checked to bf16's epsilon
# (relative Frobenius error); logits compound 16 layers of bf16 rounding
# on both programs, so they get a few epsilons.
BF16_EPS = 2.0 ** -7
GEMM_TOL = BF16_EPS
LOGITS_TOL = 4 * BF16_EPS
# (dataflow, (gm, gn, gk), exec mode) of the 2x2 mesh phase; auto is XLA's
MESH_MODES = (("summa", (2, 2, 1), "summa"),
              ("systolic", (2, 2, 1), "cannon"),
              ("splitk_summa", (2, 1, 2), "splitk_summa"),
              ("baseline", (2, 2, 1), "allgather"))


def log(msg: str) -> None:
    print(msg, flush=True)


def device_check(want: int) -> dict:
    """(a) The device as JAX reports it; exits non-zero off the TPU."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is "
                 f"{devs[0].platform!r}); this script only runs on the chip")
    if len(devs) < want:
        sys.exit(f"chip_smoke: needs {want} TPU devices, found {len(devs)}")
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"(a) device: {info}")
    return info


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want)
                 / max(float(np.linalg.norm(want)), 1e-30))


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def operands(key, m: int, n: int, k: int):
    """bf16 GEMM operands scaled so the product is O(1)."""
    ka, kb = jax.random.split(key)
    a = jax.random.normal(ka, (m, k), jnp.float32).astype(jnp.bfloat16)
    b = (jax.random.normal(kb, (k, n), jnp.float32)
         * k ** -0.5).astype(jnp.bfloat16)
    return a, b


def fp32_dot(a, b):
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def require_kernel(compiled, what: str) -> None:
    """The compiled program holds the Pallas kernel, so no GEMM went
    through the interpreter or a plain `jnp.dot`. Only the TPU backend
    emits it: a CPU rehearsal of the phases skips this check."""
    if jax.default_backend() == "tpu":
        check("tpu_custom_call" in compiled.as_text(),
              f"{what} compiled without the Pallas kernel")


def compile_timed(fn, *args):
    """AOT-compile `fn` at `args`: (compiled, seconds)."""
    t0 = time.time()
    compiled = fn.lower(*args).compile()
    return compiled, time.time() - t0


def fresh_plan_cache() -> str:
    shutil.rmtree(PLAN_CACHE, ignore_errors=True)
    PLAN_CACHE.mkdir(parents=True)
    return str(PLAN_CACHE)


def phase_local_matmul(cfg, planner, mesh, seed: int) -> None:
    """(b) The planner's inner kernel on every decode/prefill GEMM shape."""
    shapes = dict.fromkeys(
        gemm_workload(cfg, BATCH, DECODE_MAX_LEN, kind="decode")
        + gemm_workload(cfg, BATCH, PREFILL_LEN, kind="prefill"))
    key = jax.random.PRNGKey(seed)
    for i, shape in enumerate(shapes):
        ep = lower_schedule(planner.plan(shape).schedule, mesh, shape=shape)
        ik = ep.inner_kernel
        check(ik is not None,
              f"{shape}: lowering left no inner kernel ({ep.describe()})")
        a, b = operands(jax.random.fold_in(key, i), shape.m, shape.n, shape.k)
        fn = jax.jit(lambda x, y, ik=ik: local_matmul(x, y, ik))
        compiled, t_compile = compile_timed(fn, a, b)
        require_kernel(compiled, f"local_matmul {shape}")
        err = rel_err(compiled(a, b), fp32_dot(a, b))
        log(f"(b) local_matmul {shape.m}x{shape.n}x{shape.k} "
            f"ik={ik.describe()} rel_err={err:.3e} "
            f"compile {t_compile:.2f}s")
        check(err <= GEMM_TOL, f"{shape}: rel_err {err} > {GEMM_TOL}")


def phase_serve(cfg, params, mesh, grid, plan_cache: str, seed: int,
                tag: str = "(c)") -> dict:
    """(c) A routed fixed-batch serve run through `launch.serve`."""
    max_len = PROMPT + GEN
    t0 = time.time()
    planner = serve.warm_plan_cache(cfg, BATCH, PROMPT, max_len, plan_cache,
                                    grid, max_candidates=12)
    t_plan = time.time() - t0
    ctx = serve.install_gemm_context(planner, mesh)
    tracer = Tracer(process_name=f"chip_smoke.{cfg.name}")
    set_tracer(tracer)
    replicated = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    caches = jax.device_put(decode_init(params, cfg, BATCH, max_len),
                            replicated)
    rng = np.random.default_rng(seed)
    prompts = jax.device_put(
        jnp.asarray(rng.integers(0, cfg.vocab, (BATCH, PROMPT)), jnp.int32),
        replicated)
    # the jitted step reuses this trace and executable on its first call;
    # on a mesh it compiles once more for the caches' steady-state sharding
    step = jax.jit(make_serve_step(cfg))
    compiled, t_compile = compile_timed(step, params, caches,
                                        prompts[:, :1], jnp.asarray(0))
    require_kernel(compiled, "the routed serve step")
    out = serve.generate(step, params, caches, prompts, GEN)
    report = serve.build_serve_report(ctx, cfg, BATCH, max_len,
                                      plan_cache=plan_cache, plan_grid=grid,
                                      tracer=tracer)
    shard_ctx.set_gemm_context(None)
    set_tracer(None)
    for line in render_run_report(report):
        log(f"{tag}   {line}")
    log(f"{tag} serve {cfg.name} batch={BATCH} prompt={PROMPT} gen={GEN}: "
        f"plan warm-up {t_plan:.2f}s, step compile {t_compile:.2f}s, "
        f"prefill {out['t_prefill']:.2f}s, decode {out['t_gen']:.2f}s "
        f"({BATCH * GEN / max(out['t_gen'], 1e-9):.1f} tok/s, "
        f"informational)")
    return {"report": report, "first_decode": out["first_decode"]}


def phase_checks(cfg, params, served: dict, mesh=None,
                 tag: str = "(d)") -> None:
    """(d) Routing stats, then routed vs unrouted first-decode logits."""
    routing = served["report"]["routing"]
    check(routing["silent_degrades"] == 0,
          f"silent degrades: {routing['silent_degrades']}")
    check(routing["resolve_rate"] == 1.0,
          f"plan-resolve rate {routing['resolve_rate']} != 1")
    check(shard_ctx.get_gemm_context() is None, "routing still installed")
    fd = served["first_decode"]
    ref_step, t_compile = compile_timed(
        jax.jit(make_serve_step(cfg)), params, fd["caches"], fd["token"],
        fd["position"])
    ref, _ = ref_step(params, fd["caches"], fd["token"], fd["position"])
    got = fd["logits"]
    if mesh is not None:
        want = set(mesh.devices.flat)
        check(got.sharding.device_set == want,
              f"routed logits live on {len(got.sharding.device_set)} of "
              f"{len(want)} devices")
    err = rel_err(got, ref)
    agree = float(np.mean(np.argmax(np.asarray(got, np.float32), -1)
                          == np.argmax(np.asarray(ref, np.float32), -1)))
    log(f"{tag} routing: silent_degrades=0 resolve_rate=1.0; first decode "
        f"logits vs unrouted: rel_err={err:.3e} argmax agreement={agree:.3f} "
        f"(unrouted compile {t_compile:.2f}s)")
    check(err <= LOGITS_TOL, f"logits rel_err {err} > {LOGITS_TOL}")


def mesh_gemm_plans(shape, mesh, hw):
    """(exec mode, ExecPlan or None for auto) of every 2x2 mode, each with
    the closed-form inner kernel of its tiling."""
    out = [("auto", None)]
    for dataflow, (gm, gn, gk), mode in MESH_MODES:
        sched = Schedule(shape, Tiling(gm, gn, gk, tk=512), dataflow,
                         reduce_owner="round_robin" if gk > 1 else "first",
                         elem_bytes=2)
        iks = inner_kernel_candidates(sched, hw)
        if iks:
            sched = dataclasses.replace(sched, inner_kernel=iks[0])
        ep = lower_schedule(sched, mesh, shape=shape)
        check(ep.mode == mode and not ep.fallbacks,
              f"{shape} {dataflow}: lowered to {ep.describe()}")
        out.append((mode, ep))
    return out


def phase_mesh_gemms(cfg, mesh, seed: int) -> None:
    """(e) `dit_gemm` modes on the 2x2 mesh at olmo-1b GEMM shapes."""
    hw = tpu_pod_as_accelerator(tuple(mesh.devices.shape))
    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab
    shapes = (GEMMShape(BATCH, v, d), GEMMShape(BATCH, d, f),
              GEMMShape(BATCH * PREFILL_LEN, f, d))
    want = set(mesh.devices.flat)
    key = jax.random.PRNGKey(seed)
    for i, shape in enumerate(shapes):
        a, b = operands(jax.random.fold_in(key, i), shape.m, shape.n, shape.k)
        ref = fp32_dot(a, b)
        for mode, ep in mesh_gemm_plans(shape, mesh, hw):
            fn = jax.jit(lambda x, y, ep=ep, mode=mode: dit_gemm(
                x, y, mesh, mode=mode, exec_plan=ep))
            compiled, t_compile = compile_timed(fn, a, b)
            if ep is not None and ep.inner_kernel:
                require_kernel(compiled, f"dit_gemm {mode} {shape}")
            out = compiled(a, b)
            check(out.sharding.device_set == want,
                  f"{shape} {mode}: output on "
                  f"{len(out.sharding.device_set)} of {len(want)} devices")
            err = rel_err(out, ref)
            ik = ep.inner_kernel.describe() if ep and ep.inner_kernel else "-"
            log(f"(e) dit_gemm {mode:12s} {shape.m}x{shape.n}x{shape.k} "
                f"ik={ik} devices={len(out.sharding.device_set)} "
                f"rel_err={err:.3e} compile {t_compile:.2f}s")
            check(err <= GEMM_TOL, f"{shape} {mode}: rel_err {err}")


def olmo_params(cfg, mesh, seed: int):
    """Random olmo-1b weights from `seed`, replicated over `mesh`."""
    replicated = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    t0 = time.time()
    params = jax.jit(lambda k: init_params(k, cfg),
                     out_shardings=replicated)(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    n = sum(x.size for x in jax.tree.leaves(params))
    log(f"params: {cfg.name} {n / 1e9:.3f}B random from seed {seed} "
        f"in {time.time() - t0:.2f}s")
    return params


def one_chip(cfg, seed: int) -> None:
    mesh, grid = make_host_mesh(), host_mesh_grid()
    plan_cache = fresh_plan_cache()
    planner = build_planner(plan_cache, grid, max_candidates=12)
    warm_buckets(planner, gemm_workload(cfg, BATCH, DECODE_MAX_LEN,
                                        kind="decode")
                 + gemm_workload(cfg, BATCH, PREFILL_LEN, kind="prefill"))
    phase_local_matmul(cfg, planner, mesh, seed)
    params = olmo_params(cfg, mesh, seed)
    served = phase_serve(cfg, params, mesh, grid, plan_cache, seed)
    phase_checks(cfg, params, served)


def four_chips(cfg, seed: int) -> None:
    mesh = make_mesh((2, 2), ("data", "model"))
    phase_mesh_gemms(cfg, mesh, seed)
    params = olmo_params(cfg, mesh, seed)
    served = phase_serve(cfg, params, mesh, (2, 2), fresh_plan_cache(), seed,
                         tag="(f)")
    phase_checks(cfg, params, served, mesh=mesh, tag="(f)")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 mesh path (needs four chips)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and inputs")
    args = ap.parse_args()
    t0 = time.time()
    info = device_check(4 if args.four_chips else 1)
    log(f"compile cache: {enable_compile_cache()}")
    cfg = get_config(ARCH)
    (four_chips if args.four_chips else one_chip)(cfg, args.seed)
    log(f"all phases passed in {time.time() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
