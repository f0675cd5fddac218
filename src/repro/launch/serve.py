"""Serving launcher: batched prefill + decode loop against preallocated
KV caches. At startup the deployment-plan cache is warmed for the model's
GEMM workload (bucketed + exact shapes) and the decode-path schedules are
reported; repeated launches resolve plans from the persisted store instead
of re-tuning. The warmed planner is then installed as the model stack's
`GemmContext`, so every `pmm` matmul dispatches through
`dit_gemm(plan=...)` — the tuned dataflow, not a hardcoded mode, decides
each GEMM's collective pattern. At shutdown the launcher reports the
planner hit rate over the matmuls the model actually traced
(docs/architecture.md walks the full path).

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
      --batch 4 --prompt-len 32 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.deploy import Planner
from repro.deploy.batcher import BATCH_MODES, BatchPolicy
from repro.deploy.warmup import (add_plan_args, build_planner,
                                 resolve_plan_grid, warm_buckets)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import host_mesh_grid, make_host_mesh
from repro.models import shard_ctx
from repro.models.matmul import pmm
from repro.models.model import decode_init, init_params
from repro.models.workload import gemm_workload
from repro.obs import (DriftMonitor, Tracer, build_run_report,
                       render_run_report, set_tracer, write_run_report)
from repro.obs.trace import CAT_STEP, maybe_span
from repro.train.steps import make_serve_step


def warm_plan_cache(cfg, batch: int, prompt_len: int, max_len: int,
                    cache_dir: str, grid, max_candidates: int,
                    online_tune: bool = True) -> Planner:
    """Batch-tune the model's (bucketed) GEMM workload into the plan cache.

    Warms BOTH the batched-prefill shapes (M = batch*prompt_len; a real
    deployment prefills in one pass, and the persisted cache is its
    artifact) and the decode shapes (M = batch) this launcher's
    token-by-token loop actually executes, each named by a record-only
    trace of that step (`models.workload.gemm_workload`)."""
    planner = build_planner(cache_dir, grid, max_candidates,
                            online_tune=online_tune)
    prefill = gemm_workload(cfg, batch, prompt_len, kind="prefill")
    decode = gemm_workload(cfg, batch, max_len, kind="decode")
    workload = prefill + decode
    warm_buckets(planner, workload)
    plans = {shape: planner.plan(shape)          # exact shapes: warm hits or
             for shape in dict.fromkeys(workload)}   # cheap transfers
    # the decode path dominates serving; report its planned schedules
    for shape in list(dict.fromkeys(decode))[:4]:
        plan = plans[shape]
        print(f"  decode {shape.m}x{shape.n}x{shape.k}: "
              f"{plan.schedule.describe()} "
              f"est={plan.report.total_time*1e6:.2f}us [{plan.source}]")
    return planner


def install_gemm_context(planner: Planner,
                         mesh=None) -> shard_ctx.GemmContext:
    """Route the model stack's matmuls through the warmed planner: install
    the gemm context `models.matmul.pmm` consults at trace time, on `mesh`
    (default: the host mesh)."""
    ctx = shard_ctx.GemmContext(mesh=mesh or make_host_mesh(),
                                planner=planner)
    shard_ctx.set_gemm_context(ctx)
    return ctx


def load_drift(plan_cache: str, plan_grid) -> dict:
    """Drift of the persisted calibration profile vs its persisted
    measurement samples (both written by `dryrun --calibrate` next to the
    plans), or None when the cache dir carries no calibration."""
    from repro.hw.config import tpu_pod_as_accelerator
    from repro.sim import calibrate as cal
    hw = tpu_pod_as_accelerator(tuple(plan_grid))
    profile = cal.load_profile(plan_cache, hw)
    samples = cal.load_samples(plan_cache, hw)
    if profile is None or not samples:
        return None
    mon = DriftMonitor(profile)
    mon.add_samples(samples)
    return mon.summary()


def build_serve_report(ctx: shard_ctx.GemmContext, cfg, batch: int,
                       max_len: int, plan_cache: str = "",
                       plan_grid=(4, 4), tracer=None) -> dict:
    """The versioned run report: routing stats + calibration drift +
    per-dispatch provenance."""
    drift = load_drift(plan_cache, plan_grid) if plan_cache else None
    return build_run_report("serve", stats=ctx.stats.to_dict(),
                            drift=drift, tracer=tracer,
                            extra={"arch": cfg.name, "batch": batch,
                                   "max_len": max_len})


def generate(serve, params, caches, prompts, gen: int,
             temperature: float = 0.0, key=None, enc_kwargs=None) -> dict:
    """The fixed-batch loop: prefill token-by-token through the cache
    (keeps one compiled step), then decode `gen` tokens.

    Returns the generated ids `(batch, gen)`, the prefill/decode wall
    times, and `first_decode`: the caches, token and position the first
    decode step ran on, with the logits it produced — what a check against
    another program on the same cache needs."""
    enc_kwargs = enc_kwargs or {}
    prompt_len = prompts.shape[1]
    t0 = time.time()
    logits = None
    for i in range(prompt_len):
        with maybe_span("serve.prefill_token", position=i):
            logits, caches = serve(params, caches, prompts[:, i:i + 1],
                                   jnp.asarray(i), **enc_kwargs)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    generated = []
    first_decode = None
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    t0 = time.time()
    for i in range(gen):
        generated.append(np.asarray(tok)[:, 0])
        pos = jnp.asarray(prompt_len + i)
        before = caches
        with maybe_span("serve.decode_token", position=i):
            logits, caches = serve(params, caches, tok, pos, **enc_kwargs)
        if i == 0:
            first_decode = {"caches": before, "token": tok,
                            "position": pos, "logits": logits}
        if temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(
                sub, logits / temperature)[:, None].astype(jnp.int32)
        else:
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    jax.block_until_ready(logits)
    t_gen = time.time() - t0
    return {"tokens": np.stack(generated, 1), "t_prefill": t_prefill,
            "t_gen": t_gen, "first_decode": first_decode}


def run_traffic(args) -> None:
    """`--traffic` mode: replay a seeded multi-tenant trace through the
    continuous batcher against the warmed planner (docs/serving.md).

    The virtual-clock loop in `launch/traffic.py` does the SLO accounting;
    every distinct GEMM shape the replay admits is executed ONCE through
    the real routed `pmm` path on the mesh (trace-time semantics — shapes
    are static under jit, so one execution per shape is the honest unit of
    dispatch work). The run report gains a `serving` section and the
    tracer gains one marker per completed request.
    """
    from repro.launch.traffic import (TenantSpec, TrafficConfig,
                                      generate_trace, serving_section,
                                      simulate, warm_pool)
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    policy = BatchPolicy(mode=args.batch_mode)
    tenants = tuple(
        TenantSpec(name=f"tenant{i}", arch=cfg.name,
                   rate_rps=args.traffic_rate,
                   n_requests=args.traffic_requests,
                   prompt_lens=(5, 9, 13, 17), gen_lens=(2, 3, 5))
        for i in range(args.traffic_tenants))
    tcfg = TrafficConfig(seed=args.traffic_seed, tenants=tenants)
    cfgs = {t.name: cfg for t in tenants}

    planner = build_planner(args.plan_cache, args.plan_grid,
                            args.plan_candidates,
                            online_tune=not args.no_online_tune)
    warmed = warm_pool(planner, {cfg.name: cfg}, policy,
                       tcfg.max_rows(policy))
    print(f"traffic: warmed {len(warmed)} pool shape(s) "
          f"[mode={policy.mode}]")
    gemm_ctx = install_gemm_context(planner)
    tracer = Tracer(process_name=f"serve.traffic.{cfg.name}")
    set_tracer(tracer)

    def dispatch(shape, phase):
        # one real routed execution per distinct shape the replay admits
        x = jnp.zeros((shape.m, shape.k), cfg.dtype)
        w = jnp.zeros((shape.k, shape.n), cfg.dtype)
        run = jax.jit(lambda a, b: pmm(a, b, tag=f"traffic.{phase}"))
        np.asarray(run(x, w))

    trace = generate_trace(tcfg)
    t0 = time.time()
    result = simulate(trace, planner, cfgs, policy=policy,
                      precompiled=warmed, dispatch=dispatch)
    wall = time.time() - t0
    section = serving_section(result)
    for rec in result.records:
        tracer.instant("serve.request", cat=CAT_STEP, rid=rec.rid,
                       tenant=rec.tenant,
                       arrival_s=round(rec.arrival_s, 6),
                       ttft_s=round(rec.ttft_s, 6),
                       latency_s=round(rec.latency_s, 6), met=rec.met)
    print(f"traffic replay: {len(trace)} requests / "
          f"{len(tenants)} tenant(s), {section['batches']} batches, "
          f"{section['distinct_shapes']} distinct GEMM shape(s) "
          f"dispatched in {wall:.2f}s wall "
          f"({section['makespan_s']:.3f}s virtual)")
    report = build_run_report(
        "serve", stats=gemm_ctx.stats.to_dict(), tracer=tracer,
        extra={"arch": cfg.name, "serving": section,
               "traffic": {"seed": tcfg.seed, "tenants": len(tenants),
                           "requests": len(trace),
                           "rate_rps": args.traffic_rate,
                           "batch_mode": policy.mode}})
    for line in render_run_report(report):
        print(line)
    if args.run_report:
        write_run_report(args.run_report, report)
        print(f"run report: {args.run_report}")
    if args.trace:
        tracer.write(args.trace)
        print(f"chrome trace: {args.trace}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--no-plan-routing", action="store_true",
                    help="warm the cache but keep matmuls un-routed")
    ap.add_argument("--cold-serve", action="store_true",
                    help="skip the workload warm-up entirely: every traced "
                         "GEMM resolves through the planner's online "
                         "(analytic) tuning path — the real-time-planner "
                         "proof, asserted in CI from the run report")
    ap.add_argument("--refine-pending", type=int, default=0, metavar="N",
                    help="after serving, full-tune up to N bucket/analytic-"
                         "served shapes and upgrade their cache entries")
    ap.add_argument("--traffic", action="store_true",
                    help="replay a seeded multi-tenant traffic trace "
                         "through the shape-bucket-aware continuous "
                         "batcher instead of the fixed-batch loop "
                         "(docs/serving.md)")
    ap.add_argument("--traffic-requests", type=int, default=12,
                    help="requests per tenant in the replayed trace")
    ap.add_argument("--traffic-rate", type=float, default=100.0,
                    help="per-tenant Poisson arrival rate (req/s)")
    ap.add_argument("--traffic-seed", type=int, default=0,
                    help="trace seed (same seed -> identical trace)")
    ap.add_argument("--traffic-tenants", type=int, default=2,
                    help="concurrent tenants sharing the mesh + plan cache")
    ap.add_argument("--batch-mode", choices=BATCH_MODES, default="bucket",
                    help="admission policy: bucket-aware (default) or the "
                         "naive-FIFO baseline")
    ap.add_argument("--run-report", default="results/serve_run_report.json",
                    help="where to write the versioned run report "
                         "('' disables)")
    ap.add_argument("--trace", default="",
                    help="write a Perfetto-loadable Chrome trace here")
    add_plan_args(ap)
    args = ap.parse_args()
    enable_compile_cache()
    args.plan_grid = resolve_plan_grid(args, host_mesh_grid())

    if args.traffic:
        run_traffic(args)
        return

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)

    max_len = args.prompt_len + args.gen
    gemm_ctx = None
    planner = None
    tracer = None
    if not args.skip_plan_warmup:
        if args.cold_serve:
            # no warming: the planner starts empty (or with whatever the
            # cache dir already holds) and cold shapes online-tune from the
            # analytic shortlist at trace time
            planner = build_planner(args.plan_cache, args.plan_grid,
                                    args.plan_candidates,
                                    online_tune=not args.no_online_tune)
        else:
            planner = warm_plan_cache(cfg, args.batch, args.prompt_len,
                                      max_len, args.plan_cache,
                                      args.plan_grid, args.plan_candidates,
                                      online_tune=not args.no_online_tune)
        if not args.no_plan_routing:
            gemm_ctx = install_gemm_context(planner)
            tracer = Tracer(process_name=f"serve.{cfg.name}")
            set_tracer(tracer)
    caches = decode_init(params, cfg, args.batch, max_len)
    serve = jax.jit(make_serve_step(cfg))

    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)
    enc_kwargs = {}
    if cfg.is_encoder_decoder:
        enc = jnp.asarray(rng.standard_normal(
            (args.batch, cfg.n_prefix, cfg.d_model)) * 0.02, cfg.dtype)
        enc_kwargs["encoder_out"] = enc @ params["frontend_proj"]

    out = generate(serve, params, caches, prompts, args.gen,
                   temperature=args.temperature, key=jax.random.PRNGKey(1),
                   enc_kwargs=enc_kwargs)
    t_gen = out["t_gen"]
    print(f"arch={cfg.name} batch={args.batch} "
          f"prefill {args.prompt_len} tok in {out['t_prefill']:.2f}s | "
          f"decode {args.gen} tok in {t_gen:.2f}s "
          f"({args.batch * args.gen / max(t_gen, 1e-9):.1f} tok/s)")
    print("sample generations (token ids):")
    for row in out["tokens"][:2]:
        print(" ", row[:16].tolist())
    if planner is not None and args.refine_pending \
            and planner.pending_refinements:
        recs = planner.refine_pending(limit=args.refine_pending)
        print(f"refinement: full-tuned {len(recs)} online/bucket-served "
              f"shape(s); "
              f"{sum(1 for _, old, new in recs if new < old)} improved "
              f"(every refined entry is now tuned-provenance)")
    if gemm_ctx is not None:
        report = build_serve_report(gemm_ctx, cfg, args.batch, max_len,
                                    plan_cache=args.plan_cache,
                                    plan_grid=args.plan_grid, tracer=tracer)
        for line in render_run_report(report):
            print(line)
        if args.run_report:
            write_run_report(args.run_report, report)
            print(f"run report: {args.run_report}")
        if args.trace and tracer is not None:
            tracer.write(args.trace)
            print(f"chrome trace: {args.trace}")


if __name__ == "__main__":
    main()
