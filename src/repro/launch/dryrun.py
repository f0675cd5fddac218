import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (deliverable e + the §Roofline data source).

For one (arch x shape x mesh) cell:
1. FULL config: jit(step).lower(**input_specs).compile() on the production
   mesh — memory_analysis() proves the sharded program fits; the compile
   itself proves the collective schedule is coherent. Layer groups lower as
   scans (small HLO).
2. ACCOUNTING configs (1 and 2 layer-units, loop-free via accounting_mode):
   cost_analysis() + collective-byte parsing give exact per-unit FLOPs/bytes,
   extrapolated linearly to the full depth (XLA counts while bodies once —
   verified — so the full-config numbers cannot be read directly).

Each invocation handles one cell and appends JSON to --out (crash isolation;
the sweep script loops and caches).

Usage:
  python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k \
      [--multi-pod] [--skip-accounting] --out results/dryrun

With --plan-cache the cell also records the GEMMs it traces
(record-only) and reports how many the plan cache resolves. Adding --route
compiles the cell with plan routing ON — every model matmul dispatches
through its tuned dataflow's shard_map collectives on the 16x16
production mesh and the JSON reports per-reason lowering fallbacks (the
ROADMAP routed-compile proof; pair with --skip-accounting to keep the
measurement to the one routed compile).
--route-dataflows restricts the warm-up's candidate search, e.g.
`--route-dataflows systolic_over_summa` proves the Fig. 6c outer-systolic
mode executes on the production mesh (see docs/dataflows.md).
--calibrate closes the measurement loop first: every executable mode runs
on a --plan-grid mesh of local devices, a CalibrationProfile is fitted and
persisted into --plan-cache, the warm-up tunes with the measured cost
model, and the JSON gains a 'calibration' section (fit quality + how many
of this cell's tuning decisions the calibration flipped). See
docs/plan-lifecycle.md "Calibration".

With --plan-cache the cell additionally installs the structured dispatch
tracer (repro.obs) and writes, next to the cell JSON: <tag>.run_report.json
(the versioned machine-readable report CI asserts on — routing counters,
per-dispatch plan provenance, workload plan-resolve rate, calibration fit,
predicted-vs-measured drift) and <tag>.trace.json (Chrome trace-event
spans, loadable at ui.perfetto.dev). See docs/observability.md.
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, cells, get_config, list_archs
from repro.launch import flops as flops_lib
from repro.launch.hlo_stats import collective_stats
from repro.launch.input_specs import input_specs, param_shapes, cache_shapes
from repro.launch.mesh import make_production_mesh
from repro.models import accounting
from repro.models.common import ModelConfig
from repro.models.model import decode_step, forward
from repro.optim import adamw
from repro.parallel.spec_rules import (batch_spec, cache_shardings, dp_axes,
                                       param_shardings)
from repro.train.steps import make_serve_step, make_train_step
from jax.sharding import NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# accounting-unit reduction per arch family
# ---------------------------------------------------------------------------

def accounting_configs(cfg: ModelConfig) -> Tuple[ModelConfig, ModelConfig,
                                                  float, int, int]:
    """(cfg_small, cfg_big, units_full, units_small, units_big): linear
    extrapolation F_full = F_s + (units_full - u_s)/(u_b - u_s) * (F_b - F_s)."""
    if cfg.is_encoder_decoder:
        c1 = dataclasses.replace(cfg, n_layers=1, n_encoder_layers=1)
        c2 = dataclasses.replace(cfg, n_layers=2, n_encoder_layers=2)
        return c1, c2, cfg.n_layers, 1, 2
    if cfg.block_pattern == "mamba2_hybrid":
        per = cfg.hybrid_attn_every
        c1 = dataclasses.replace(cfg, n_layers=per)
        c2 = dataclasses.replace(cfg, n_layers=2 * per)
        return c1, c2, cfg.n_layers / per, 1, 2
    if cfg.block_pattern == "xlstm":
        per = cfg.slstm_every
        c1 = dataclasses.replace(cfg, n_layers=per)
        c2 = dataclasses.replace(cfg, n_layers=2 * per)
        return c1, c2, cfg.n_layers // per, 1, 2
    if cfg.n_experts and cfg.n_dense_layers:
        # keep the dense layer in the base; delta = one MoE layer
        c1 = dataclasses.replace(cfg, n_layers=cfg.n_dense_layers + 1)
        c2 = dataclasses.replace(cfg, n_layers=cfg.n_dense_layers + 2)
        return c1, c2, cfg.n_layers - cfg.n_dense_layers, 1, 2
    c1 = dataclasses.replace(cfg, n_layers=1)
    c2 = dataclasses.replace(cfg, n_layers=2)
    return c1, c2, cfg.n_layers, 1, 2


# ---------------------------------------------------------------------------
# cell builders
# ---------------------------------------------------------------------------

MICROBATCHES = int(os.environ.get("DIT_MICROBATCHES", "1"))


def build_lowered(cfg: ModelConfig, shape_name: str, mesh,
                  donate: bool = True):
    """Lower the cell's step function with production shardings."""
    specs = input_specs(cfg, shape_name)
    kind = specs["kind"]
    pshapes = param_shapes(cfg)
    pshard = param_shardings(pshapes, mesh)
    bspec = NamedSharding(mesh, batch_spec(mesh))

    if kind == "train":
        opt = adamw.AdamWConfig()
        ostate_shapes = jax.eval_shape(lambda p: adamw.init(p), pshapes)
        oshard = jax.tree.map(
            lambda l: NamedSharding(mesh, P()) if l.ndim == 0 else None,
            ostate_shapes)
        # moments follow the param shardings; scalar step replicated
        oshard = adamw.AdamWState(
            step=NamedSharding(mesh, P()),
            mu=param_shardings(ostate_shapes.mu, mesh),
            nu=param_shardings(ostate_shapes.nu, mesh))
        step_raw = make_train_step(cfg, opt, microbatches=MICROBATCHES,
                                   compress_grads=False)

        def train_fn(params, opt_state, batch):
            p, o, _, m = step_raw(params, opt_state, None, batch)
            return p, o, m["loss"]

        bshard = jax.tree.map(lambda l: bspec, specs["inputs"])
        fn = jax.jit(
            train_fn,
            in_shardings=(pshard, oshard, bshard),
            out_shardings=(pshard, oshard, NamedSharding(mesh, P())),
            donate_argnums=(0, 1) if donate else ())
        return fn.lower(pshapes, ostate_shapes, specs["inputs"]), specs

    if kind == "prefill":
        # serving semantics: prefill fills the cache and emits ONLY the
        # last position's logits (§Perf iteration 12 — returning the full
        # (B,S,V) fp32 logits cost seamless 135 GB/device).
        def prefill_fn(params, tokens, *extra):
            kwargs = {}
            i = 0
            if cfg.frontend == "vision_stub":
                kwargs["prefix_embeds"] = extra[i]; i += 1
            if cfg.is_encoder_decoder:
                kwargs["encoder_embeds"] = extra[i]; i += 1
            hidden = forward(params, tokens, cfg, remat=False,
                             return_hidden=True, **kwargs)
            from repro.models.matmul import weight_value
            from repro.models.model import lm_head_weight
            return (hidden[:, -1] @ weight_value(lm_head_weight(params, cfg))
                    ).astype(jnp.float32)

        args = [pshapes, specs["tokens"]]
        in_sh = [pshard, bspec]
        for key in ("prefix_embeds", "encoder_embeds"):
            if key in specs:
                args.append(specs[key])
                in_sh.append(bspec)
        vocab_ax = "model" if cfg.vocab % mesh.shape["model"] == 0 else None
        fn = jax.jit(prefill_fn, in_shardings=tuple(in_sh),
                     out_shardings=NamedSharding(
                         mesh, P(dp_axes(mesh), vocab_ax)))
        return fn.lower(*args), specs

    # decode
    cshapes = specs["caches"]
    cshard = cache_shardings(cshapes, mesh, cfg, specs["batch"])
    tok_sh = NamedSharding(
        mesh, batch_spec(mesh) if specs["batch"] % _dp_size(mesh) == 0
        else P(None, None))

    def decode_fn(params, caches, tokens, position, *extra):
        enc = extra[0] if extra else None
        logits, new_caches = decode_step(params, caches, tokens, position,
                                         cfg, encoder_out=enc)
        return logits, new_caches

    args = [pshapes, cshapes, specs["tokens"], specs["position"]]
    in_sh = [pshard, cshard, tok_sh, NamedSharding(mesh, P())]
    if "encoder_out" in specs:
        args.append(specs["encoder_out"])
        in_sh.append(tok_sh if specs["batch"] % _dp_size(mesh) == 0
                     else NamedSharding(mesh, P(None, None, None)))
    fn = jax.jit(decode_fn, in_shardings=tuple(in_sh),
                 out_shardings=(NamedSharding(mesh, P()), cshard),
                 donate_argnums=(1,) if donate else ())
    return fn.lower(*args), specs


def _dp_size(mesh) -> int:
    n = 1
    for a in dp_axes(mesh):
        n *= mesh.shape[a]
    return n


def _cost_analysis(compiled) -> Dict[str, float]:
    """compiled.cost_analysis() normalized to one dict: jax returns a plain
    dict for most executables but a per-module list for some partitioned
    programs (observed with routed shard_map matmuls in the step)."""
    ca = compiled.cost_analysis() or {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return ca


# ---------------------------------------------------------------------------
# per-cell run
# ---------------------------------------------------------------------------

def calibrate_plan_cache(plan_cache: str, plan_grid, reps: int = 1
                         ) -> Dict[str, Any]:
    """Fit the SoftHier cost model to this host's measured mode efficiency.

    Runs `sim.calibrate.measure_modes` on a `plan_grid` mesh carved out of
    the local devices (every executable mode over the GEMM grid, lowering
    asserted clean), least-squares-fits a `CalibrationProfile`, and persists
    it NEXT TO THE PLANS keyed by the pod-view hardware fingerprint — the
    same profile `deploy.warmup.build_planner` auto-loads, so every later
    warm-up from this cache dir tunes with the measured cost model.
    Returns the JSON `calibration` section (fit stats + measurement count).
    """
    from repro.hw.config import tpu_pod_as_accelerator
    from repro.launch.mesh import make_mesh
    from repro.sim import calibrate as cal

    rows, cols = plan_grid
    if rows != cols or rows < 4:
        # the mode-case table needs a square grid for the cannon ring and
        # >= 4x4 for a non-degenerate outer ring of 2x2 inner groups —
        # fail with the requirement, not a deep clean-lowering assertion
        raise ValueError(
            f"--calibrate requires a square --plan-grid of at least 4x4 "
            f"(every executable mode must lower cleanly on the "
            f"measurement mesh); got {rows}x{cols}")
    hw = tpu_pod_as_accelerator(tuple(plan_grid))
    mesh = make_mesh(tuple(plan_grid), ("data", "model"))
    # the profile persisted by the PREVIOUS calibration run (if any): the
    # fresh measurements below, compared against ITS predictions, quantify
    # how far the machine drifted since it was fitted
    prior = cal.load_profile(plan_cache, hw)
    t0 = time.time()
    profile, samples = cal.calibrate_mesh(hw, mesh, reps=reps)
    path = cal.save_profile(plan_cache, profile)
    cal.save_samples(plan_cache, profile.hw_digest, samples)
    print(f"calibration: {profile.describe()} from {len(samples)} "
          f"measurements in {time.time()-t0:.1f}s -> {path}", flush=True)
    out = {
        "profile": profile.to_dict(),
        "profile_digest": profile.digest(),
        "samples": len(samples),
        "fit_ok": profile.fit_ok,
        "rank_agreement_before": profile.rank_agreement_before,
        "rank_agreement_after": profile.rank_agreement_after,
        "picks_measured_ratio": profile.picks_measured_ratio,
    }
    if prior is not None:
        from repro.obs import DriftMonitor
        mon = DriftMonitor(prior)
        mon.add_samples(samples)
        out["drift_vs_prior"] = mon.summary()
    return out


def calibration_rank_flips(planner, workload) -> Dict[str, Any]:
    """Re-tune the workload with and without the planner's profile and
    count schedules the calibrated ranking changed (fresh searches on both
    sides — the cache is not consulted, and BOTH searches enumerate the
    same dataflow space, so the report isolates the ranking effect of the
    measured scale factors from the trusted profile's search-space
    widening)."""
    from repro.core.autotuner import default_dataflows, tune
    from repro.sim.calibrate import is_trusted

    flips, flipped = 0, []
    shapes = list(dict.fromkeys(workload))
    out = {"workload_shapes": len(shapes), "trusted": True}
    if not is_trusted(planner.calibration):
        # an untrusted profile is defined to change no ranking (the tuner
        # ignores it), so the two searches below would be identical —
        # report the foregone conclusion without paying 2N candidate
        # searches
        return {**out, "trusted": False, "rank_flips": 0, "flipped": []}
    space = planner.dataflows or default_dataflows(planner.calibration)
    for shape in shapes:
        kw = dict(dataflows=space,
                  elem_bytes=planner.elem_bytes,
                  max_candidates=planner.max_candidates,
                  store_stage_options=planner.store_stage_options)
        try:
            base = tune(shape, planner.hw, **kw)
            calib = tune(shape, planner.hw, calibration=planner.calibration,
                         **kw)
        except RuntimeError:
            continue
        if base.schedule != calib.schedule:
            flips += 1
            flipped.append({"shape": [shape.m, shape.n, shape.k],
                            "analytical": base.schedule.describe(),
                            "calibrated": calib.schedule.describe()})
    return {**out, "rank_flips": flips, "flipped": flipped}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             skip_accounting: bool = False,
             plan_cache: str = "",
             plan_grid=(4, 4),
             route: bool = False,
             route_dataflows=None,
             calibrate: bool = False) -> Dict[str, Any]:
    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    from repro.models import shard_ctx
    shard_ctx.set_mesh(mesh)   # pin activation layouts during tracing
    gemm_ctx = None
    calibration_out = None
    if plan_cache:
        # structured dispatch tracer: every pmm the cell traces emits a
        # provenance span; main() exports <tag>.trace.json + the run report
        from repro.obs import Tracer, set_tracer
        set_tracer(Tracer(process_name=f"dryrun.{arch}.{shape_name}"))
    if calibrate:
        # fit + persist BEFORE the planner is built so the warm-up below
        # already tunes with the measured cost model
        calibration_out = calibrate_plan_cache(plan_cache, plan_grid)
    if plan_cache:
        # Default: record-only gemm context — every pmm the cell traces is
        # logged so the JSON can check the warmed plan cache against the
        # GEMMs this (arch x shape x mesh) really runs, while the compile
        # measures the untouched production program. --route flips the
        # context live: the cell's workload is warmed into the planner and
        # every model matmul compiles through its tuned dataflow's
        # shard_map collectives on the production mesh (the ROADMAP "16x16
        # routed compile proof"), with per-reason fallback counts in the
        # JSON — no silent auto degrades.
        from repro.deploy.warmup import build_planner, warm_buckets
        planner = build_planner(plan_cache, plan_grid, max_candidates=12,
                                dataflows=route_dataflows)
        if route or calibration_out is not None:
            from repro.models.workload import gemm_workload
            spec = SHAPES[shape_name]
            workload = gemm_workload(cfg, spec["global_batch"],
                                     spec["seq_len"], kind=spec["kind"],
                                     mesh=mesh)
        if calibration_out is not None:
            # how many of this cell's tuning decisions the measured scale
            # factors actually changed (fresh searches both sides)
            calibration_out.update(calibration_rank_flips(planner, workload))
        if route:
            warm_buckets(planner, workload)
            planner.batch_tune(workload, allow_bucketed=True,
                               skip_illegal=route_dataflows is not None)
            gemm_ctx = shard_ctx.GemmContext(mesh=mesh, planner=planner)
        else:
            gemm_ctx = shard_ctx.GemmContext(mesh=None, planner=planner)
        shard_ctx.set_gemm_context(gemm_ctx)
    out: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "multi_pod": multi_pod,
        "mesh": dict(zip(mesh.axis_names, mesh.devices.shape)),
        "routed": bool(route),
    }
    if calibration_out is not None:
        out["calibration"] = calibration_out
    t0 = time.time()

    # 1. FULL config: compile + memory analysis
    lowered, specs = build_lowered(cfg, shape_name, mesh)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    out["full"] = {
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "argument_bytes_per_device": int(getattr(mem, "argument_size_in_bytes", 0)),
        "output_bytes_per_device": int(getattr(mem, "output_size_in_bytes", 0)),
        "temp_bytes_per_device": int(getattr(mem, "temp_size_in_bytes", 0)),
        "peak_bytes_per_device": int(
            getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0)),
    }
    # cost_analysis is PER-DEVICE on the partitioned module (verified
    # empirically); scale by n_chips for global numbers. Loop bodies are
    # counted once, hence the accounting configs below for the real terms.
    ca = _cost_analysis(compiled)
    out["full"]["hlo_flops_raw"] = float(ca.get("flops", 0.0)) * n_chips
    out["full"]["hlo_bytes_raw"] = float(ca.get("bytes accessed", 0.0)) * n_chips
    cs = collective_stats(compiled.as_text())
    out["full"]["collective_bytes_raw"] = cs.total_bytes * n_chips
    out["full"]["collectives"] = cs.summary()
    del compiled, lowered

    if gemm_ctx is not None:
        observed = gemm_ctx.stats.observed_shapes()
        planner = gemm_ctx.planner
        resolved = sum(1 for s in observed
                       if planner.plan_cached(s) is not None)
        out["workload"] = {
            "observed": len(observed),
            "plan_resolved": resolved,
            "plan_resolve_rate": resolved / len(observed) if observed else 0.0,
        }
        if route:
            st = gemm_ctx.stats
            out["routing"] = {
                "modes": dict(sorted(st.modes.items())),
                "degrade_reasons": dict(sorted(st.degrades.items())),
                # degraded == landed on auto; reasons like non_square_systolic
                # or a scatter demotion still execute a tuned dataflow
                "degraded": st.modes.get("auto", 0),
                "silent_auto_degrades": st.silent_degrades,
                "layer_weights_in_place": st.in_place,
                "layer_weights_sliced": st.sliced,
                "hits": st.hits, "bucketed": st.bucketed,
                "fallback": st.fallback,
                "resolve_rate": st.resolve_rate,
            }

    if plan_cache:
        # predicted-vs-measured drift of the persisted calibration profile
        # against the persisted measurement samples (written next to it by
        # --calibrate; present on this run when --calibrate just ran, or
        # from an earlier calibration of the same cache dir)
        from repro.hw.config import tpu_pod_as_accelerator
        from repro.obs import DriftMonitor
        from repro.sim import calibrate as cal
        hw_pod = tpu_pod_as_accelerator(tuple(plan_grid))
        profile = cal.load_profile(plan_cache, hw_pod)
        samples = cal.load_samples(plan_cache, hw_pod)
        if profile is not None and samples:
            mon = DriftMonitor(profile)
            mon.add_samples(samples)
            out["drift"] = mon.summary()

    # 2. accounting configs for the roofline terms
    if not skip_accounting:
        c1, c2, units_full, u1, u2 = accounting_configs(cfg)
        vals = {}
        for tag, c in (("small", c1), ("big", c2)):
            with accounting.accounting_mode(specs["seq"]):
                low, _ = build_lowered(c, shape_name, mesh, donate=False)
                comp = low.compile()
            cai = _cost_analysis(comp)
            csi = collective_stats(comp.as_text())
            vals[tag] = {         # x n_chips: per-device -> global
                "flops": float(cai.get("flops", 0.0)) * n_chips,
                "bytes": float(cai.get("bytes accessed", 0.0)) * n_chips,
                "coll": float(csi.total_bytes) * n_chips,
            }
            del comp, low
        scale = (units_full - u1) / (u2 - u1)
        extr = {k: vals["small"][k] + scale * (vals["big"][k] - vals["small"][k])
                for k in ("flops", "bytes", "coll")}
        mf = flops_lib.model_flops(cfg, specs["batch"], specs["seq"], specs["kind"])
        extr["flops"] += mf["slstm_correction"]
        out["accounting"] = {
            "per_unit": vals, "units_full": units_full,
            "hlo_flops": extr["flops"], "hlo_bytes": extr["bytes"],
            "collective_bytes": extr["coll"],
            "model_flops": mf["total"],
            "model_flops_breakdown": mf,
            "useful_ratio": mf["total"] / extr["flops"] if extr["flops"] else 0.0,
        }
        # roofline terms (single-pod constants; per-chip)
        PEAK, HBM, ICI = 197e12, 819e9, 50e9 * 4   # bf16 peak, HBM bw, 4 links
        out["roofline"] = {
            "compute_s": extr["flops"] / (n_chips * PEAK),
            "memory_s": extr["bytes"] / (n_chips * HBM),
            "collective_s": extr["coll"] / (n_chips * ICI),
        }
        dom = max(out["roofline"], key=out["roofline"].get)
        out["roofline"]["dominant"] = dom
        tot = max(out["roofline"]["compute_s"], out["roofline"]["memory_s"],
                  out["roofline"]["collective_s"])
        out["roofline"]["roofline_fraction"] = (
            out["roofline"]["compute_s"] / tot if tot else 0.0)

    out["elapsed_s"] = round(time.time() - t0, 1)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--skip-accounting", action="store_true")
    ap.add_argument("--plan-cache", default="",
                    help="warmed plan-cache dir; enables the record-only "
                         "gemm context + workload plan-resolve report")
    ap.add_argument("--plan-grid", type=int, nargs=2, default=(4, 4),
                    metavar=("R", "C"),
                    help="pod grid the cache was warmed for (fingerprint)")
    ap.add_argument("--route", action="store_true",
                    help="compile with plan routing ON: warm the planner for "
                         "this cell's workload and dispatch every model "
                         "matmul through its tuned dataflow's collectives "
                         "on the production mesh (requires --plan-cache); "
                         "the JSON gains a 'routing' section with "
                         "per-reason fallback counts")
    ap.add_argument("--route-dataflows", nargs="+", default=None,
                    metavar="DF",
                    help="restrict the warm-up's candidate search to these "
                         "schedule dataflows (e.g. systolic_over_summa to "
                         "prove the Fig. 6c outer-systolic mode on the "
                         "production mesh); shapes with no legal restricted "
                         "schedule stay unplanned and dispatch as auto "
                         "fallbacks")
    ap.add_argument("--calibrate", action="store_true",
                    help="fit the SoftHier cost model to measured mode "
                         "efficiency before warming: run every executable "
                         "mode on a --plan-grid mesh of local devices, "
                         "least-squares-fit per-resource scale factors, "
                         "persist the profile into --plan-cache (keyed by "
                         "hw fingerprint, auto-loaded by later warm-ups), "
                         "re-tune this cell's workload and report rank-flip "
                         "counts in the JSON 'calibration' section")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()
    if args.route and not args.plan_cache:
        ap.error("--route requires --plan-cache")
    if args.route_dataflows and not args.route:
        ap.error("--route-dataflows requires --route")
    if args.calibrate and not args.plan_cache:
        ap.error("--calibrate requires --plan-cache (the profile persists "
                 "next to the plans it calibrates)")

    os.makedirs(args.out, exist_ok=True)
    tag = f"{args.arch}__{args.shape}__{'mp' if args.multi_pod else 'sp'}"
    if args.route:
        tag += "__routed"
    path = os.path.join(args.out, tag + ".json")
    try:
        result = run_cell(args.arch, args.shape, args.multi_pod,
                          skip_accounting=args.skip_accounting,
                          plan_cache=args.plan_cache,
                          plan_grid=args.plan_grid,
                          route=args.route,
                          route_dataflows=args.route_dataflows,
                          calibrate=args.calibrate)
        result["status"] = "ok"
    except Exception as e:
        result = {"arch": args.arch, "shape": args.shape,
                  "multi_pod": args.multi_pod, "status": "error",
                  "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-3000:]}
    with open(path, "w") as f:
        json.dump(result, f, indent=1)

    # observability artifacts alongside the cell JSON: the versioned run
    # report (what CI asserts on) + the Perfetto-loadable dispatch trace.
    # launch/report.py skips both suffixes when globbing cells.
    from repro.models import shard_ctx
    from repro.obs import build_run_report, get_tracer, write_run_report
    ctx = shard_ctx.get_gemm_context()
    tracer = get_tracer()
    if ctx is not None or tracer is not None:
        run_report = build_run_report(
            "dryrun",
            stats=ctx.stats.to_dict() if ctx is not None else None,
            workload=result.get("workload"),
            drift=result.get("drift"),
            calibration=result.get("calibration"),
            tracer=tracer,
            extra={"arch": args.arch, "shape": args.shape,
                   "multi_pod": args.multi_pod, "routed": args.route,
                   "status": result["status"]})
        rr_path = write_run_report(
            os.path.join(args.out, tag + ".run_report.json"), run_report)
        print(f"run report -> {rr_path}")
        if tracer is not None:
            print(f"trace -> "
                  f"{tracer.write(os.path.join(args.out, tag + '.trace.json'))}")

    print(json.dumps({k: v for k, v in result.items() if k != "traceback"},
                     indent=1))
    if result["status"] != "ok":
        raise SystemExit(1)


if __name__ == "__main__":
    main()
