"""Training launcher.

Smoke scale (this host):   PYTHONPATH=src python -m repro.launch.train \
    --arch olmo-1b --smoke --steps 200 --batch 8 --seq 128
Production (a real pod):   same command without --smoke; the mesh comes from
    make_production_mesh() and params/optimizer are sharded by spec_rules.

Features: deterministic stateless data, microbatching, optional int8 gradient
compression on the DP all-reduce, atomic checkpoints + auto-resume, heartbeat
files, straggler logging — the full DESIGN.md §5 story.

At startup the deployment-plan cache is warmed for the training workload and
installed as the model stack's gemm context, so the forward/backward matmuls
route through `dit_gemm(exec_plan=...)` (all dataflow modes are scan-based
and reverse-differentiable). `--skip-plan-warmup` turns both off. The
shutdown routing line includes the executed-mode histogram and per-reason
degrade counts from the schedule->mesh lowering (repro.core.lower).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, smoke_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.deploy.warmup import add_plan_args, resolve_plan_grid
from repro.ft.runtime import LoopConfig, run_training
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import shard_ctx
from repro.models.model import init_params
from repro.obs import (Tracer, build_run_report, render_run_report,
                       set_tracer, write_run_report)
from repro.optim import adamw, compress
from repro.parallel.spec_rules import param_shardings
from repro.train.steps import make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + host mesh (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--run-report", default="results/train_run_report.json",
                    help="where to write the versioned run report "
                         "('' disables)")
    ap.add_argument("--trace", default="",
                    help="write a Perfetto-loadable Chrome trace here")
    add_plan_args(ap)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.smoke:
        mesh = make_host_mesh()
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        shard_ctx.set_mesh(mesh)
    args.plan_grid = resolve_plan_grid(
        args, (mesh.shape["data"], mesh.shape["model"]))

    gemm_ctx = None
    tracer = None
    if not args.skip_plan_warmup:
        from repro.deploy.warmup import build_planner, warm_buckets
        from repro.models.workload import gemm_workload
        planner = build_planner(args.plan_cache, args.plan_grid,
                                args.plan_candidates)
        workload = gemm_workload(cfg, args.batch, args.seq, kind="train",
                                 mesh=shard_ctx.get_mesh())
        warm_buckets(planner, workload)
        # exact shapes: warm hits or cheap bucketed transfers, never a
        # second full search on top of the bucket tunes above
        planner.batch_tune(workload, allow_bucketed=True)
        gemm_ctx = shard_ctx.GemmContext(mesh=mesh, planner=planner)
        shard_ctx.set_gemm_context(gemm_ctx)
        tracer = Tracer(process_name=f"train.{cfg.name}")
        set_tracer(tracer)

    opt = adamw.AdamWConfig(lr=args.lr, warmup_steps=max(5, args.steps // 20),
                            total_steps=args.steps)
    params = init_params(jax.random.PRNGKey(0), cfg)
    if not args.smoke:
        shardings = param_shardings(jax.eval_shape(lambda: params), mesh)
        params = jax.tree.map(jax.device_put, params, shardings)
    opt_state = adamw.init(params)
    comp_state = compress.init(params) if args.compress_grads else None

    raw = jax.jit(make_train_step(cfg, opt, microbatches=args.microbatches,
                                  compress_grads=args.compress_grads))

    def step_fn(state, batch):
        p, o, c = state
        p, o, c, m = raw(p, o, c, batch)
        return (p, o, c), m

    data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                                  global_batch=args.batch))
    t_last = [time.time()]

    def on_metrics(step, m):
        if step % args.log_every == 0:
            dt = time.time() - t_last[0]
            t_last[0] = time.time()
            toks = args.batch * args.seq * args.log_every
            print(f"step {step:5d} loss {float(m['loss']):.4f} "
                  f"lr {float(m['lr']):.2e} gnorm {float(m.get('grad_norm', 0)):.2f} "
                  f"tok/s {toks / max(dt, 1e-9):,.0f}", flush=True)

    run_training(step_fn, (params, opt_state, comp_state), data,
                 LoopConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                            ckpt_dir=args.ckpt_dir),
                 make_batch_arrays=lambda b: {k: jnp.asarray(v)
                                              for k, v in b.items()},
                 on_metrics=on_metrics)
    if gemm_ctx is not None:
        from repro.launch.serve import load_drift
        drift = load_drift(args.plan_cache, args.plan_grid)
        report = build_run_report(
            "train", stats=gemm_ctx.stats.to_dict(), drift=drift,
            tracer=tracer,
            extra={"arch": cfg.name, "steps": args.steps,
                   "batch": args.batch, "seq": args.seq})
        for line in render_run_report(report):
            print(line)
        if args.run_report:
            write_run_report(args.run_report, report)
            print(f"run report: {args.run_report}")
        if args.trace and tracer is not None:
            tracer.write(args.trace)
            print(f"chrome trace: {args.trace}")
    print("done.")


if __name__ == "__main__":
    main()
