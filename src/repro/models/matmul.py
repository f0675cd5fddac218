"""Plan-routed matmul: the single funnel every dense model matmul goes
through, so deployment schedules — not hand-written call sites — decide how
each GEMM executes (the paper's core claim, applied to the model stack).

`pmm(x, w, tag=...)` is a drop-in replacement for `x @ w`:

- with no `GemmContext` installed it IS `x @ w` (bit-for-bit — smoke tests
  and meshless tracing are unchanged);
- with a record-only context (mesh=None) it additionally logs the
  (tag, GEMMShape) pair it would have routed: the workload
  `repro.models.workload.gemm_workload` hands the planner to warm;
- with a live mesh+planner context it flattens leading batch/seq dims to a
  2-D GEMM, consults the planner's warmed cache (exact hit, else bucketed
  transfer, else an online tune over the closed-form analytic shortlist —
  never a full tune on the dispatch path), and dispatches through
  `repro.core.gemm.dit_gemm`, which maps the tuned dataflow onto mesh
  collectives. Shapes with no usable plan still route through `dit_gemm`'s
  auto mode and are counted as fallbacks in the context stats.

The planner consult happens at trace time (GEMM shapes are static under
jit), so routing costs nothing per executed step.

Batched einsums that are not single dense GEMMs (MoE expert batches, MLA's
absorbed-form contractions) keep their einsum form but log their per-GEMM
shape via `record_gemm` so the observed workload stays complete.

`w` may be a `StoredWeight`: the weight as it is stored, not copied out.
The serve step's layer loop hands each layer's GEMMs one layer of a
stacked weight; a tied LM head is handed the embedding (vocab, D), the
transpose of what it multiplies by. A routed GEMM whose kernel can read
the weight so, in few enough row blocks, does (counted `in_place` for a
layer, `read_transposed` for a transpose); any other is handed the
(K, N) matrix copied out (counted `sliced`, `copied_transposed`;
`core.gemm.reads_in_place`).

See docs/architecture.md (routing path) and docs/plan-lifecycle.md (how the
plans pmm consults are produced, cached, and refined).
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.schedule import AttnShape, GEMMShape
from repro.models import shard_ctx
from repro.obs import trace as obs_trace


@dataclasses.dataclass(frozen=True)
class StoredWeight:
    """A (K, N) GEMM weight where and as it is stored: layer `layer` of a
    stacked weight `stored` (L, ...), or `stored` itself with no layer;
    with `transposed`, stored as its transpose (N, K), as a tied LM head's
    embedding is. GEMMs that can read it so do; the rest take `value()`,
    the (K, N) matrix copied out."""
    stored: jax.Array
    layer: Optional[jax.Array] = None
    transposed: bool = False

    @property
    def shape(self):
        s = self.stored.shape if self.layer is None else self.stored.shape[1:]
        return (*s[:-2], s[-1], s[-2]) if self.transposed else s

    @property
    def ndim(self) -> int:
        return len(self.shape)

    @property
    def dtype(self):
        return self.stored.dtype

    def value(self) -> jax.Array:
        w = self.stored
        if self.layer is not None:
            w = jax.lax.dynamic_index_in_dim(w, self.layer, keepdims=False)
        return jnp.swapaxes(w, -1, -2) if self.transposed else w


def weight_value(w) -> jax.Array:
    """`w` as an array: a `StoredWeight`'s matrix copied out, else `w`."""
    return w.value() if isinstance(w, StoredWeight) else w


def _gemm_shape(x: jax.Array, w: jax.Array) -> GEMMShape:
    """The 2-D problem `x @ w` solves: leading dims of x flatten into M."""
    return GEMMShape(m=int(math.prod(x.shape[:-1])), n=int(w.shape[-1]),
                     k=int(x.shape[-1]))


def _routable(x: jax.Array, w: jax.Array) -> bool:
    return (w.ndim == 2 and x.ndim >= 2 and x.shape[-1] == w.shape[0]
            and all(int(d) > 0 for d in x.shape))


def record_gemm(tag: str, m: int, n: int, k: int, count: int = 1) -> None:
    """Log a GEMM executed outside `pmm` (batched expert einsums etc.) so the
    observed workload covers everything the model runs. `count` > 1 logs one
    einsum that stands for `count` independent contractions of this shape
    (MLA's absorbed form runs one per head)."""
    ctx = shard_ctx.get_gemm_context()
    if ctx is not None and m > 0 and n > 0 and k > 0 and count > 0:
        ctx.stats.record(tag, GEMMShape(m, n, k), count=count)


def lookup_plan(planner, shape: GEMMShape):
    """Dispatch-path plan lookup:
    (plan | None, 'hit' | 'bucketed' | 'analytic' | None).

    Never runs a full tune — serving traffic must not pay a candidate search
    at trace time; cold shapes are online-tuned from the bounded analytic
    shortlist, and only a shape with no legal shortlist candidate falls back
    to the auto dataflow (counted in the stats). Classification follows the
    served plan's provenance: 'hit' = born from a full tune, 'bucketed' =
    adapted from a nearby tuned shape, 'analytic' = priced online from the
    closed-form shortlist (whether the transfer/tune happened now or on an
    earlier lookup).
    """
    plan = planner.plan_cached(shape)
    if plan is None:
        return None, None
    # literals == deploy.plan.SOURCE_BUCKETED / SOURCE_ANALYTIC (string
    # literals keep the model layer's imports free of the deploy package)
    source = getattr(plan, "source", "")
    kind = source if source in ("bucketed", "analytic") else "hit"
    return plan, kind


def _dispatch_routed(ctx, x: jax.Array, w: jax.Array, shape: GEMMShape,
                     prov: dict, tracer) -> jax.Array:
    """The routed dispatch: plan consult -> lowering -> dit_gemm.

    `prov` is the span's provenance record (also lifted into the run
    report): plan-resolve latency, hit/bucketed/fallback classification,
    plan + calibration digests, the resolved mode with its fallback-reason
    chain, and the plan's predicted cost. Digests are only computed when a
    tracer is installed — they serialize the plan, which the untraced
    dispatch path must not pay for.
    """
    from repro.core.gemm import dit_gemm   # lazy: keep import cycles at bay
    plan, kind = None, None
    if ctx.planner is not None:
        t0 = time.perf_counter()
        plan, kind = lookup_plan(ctx.planner, shape)
        resolve_us = (time.perf_counter() - t0) * 1e6
        prov["plan_resolve_us"] = round(resolve_us, 1)
        if tracer is not None:
            tracer.metrics.observe("pmm.plan_resolve_us", resolve_us)
        if kind == "hit":
            ctx.stats.hits += 1
        elif kind == "bucketed":
            ctx.stats.bucketed += 1
        elif kind == "analytic":
            ctx.stats.analytic += 1
    if plan is None:
        ctx.stats.fallback += 1
        if isinstance(w, StoredWeight):
            ctx.stats.record_stored(w.layer is not None, w.transposed, False)
        # inner_kernel/overlap are part of the per-dispatch contract: every
        # dispatch record carries them (None/False = XLA-picked local GEMM),
        # so drift monitoring can attribute a regression to the inner level
        # without special-casing fallbacks
        prov.update(provenance="fallback", mode="auto",
                    inner_kernel=None, overlap=False)
        return dit_gemm(x, weight_value(w), ctx.mesh, mode="auto",
                        row_axis=ctx.row_axis, col_axis=ctx.col_axis)
    # lower the tuned schedule here (not inside dit_gemm) so the resolved
    # mode and any fallback reasons land in the context stats — launchers
    # report WHY routing degraded, not just that it did
    from repro.core.lower import lower_schedule
    exec_plan = lower_schedule(getattr(plan, "schedule", plan), ctx.mesh,
                               ctx.row_axis, ctx.col_axis, shape=shape)
    ctx.stats.record_lowering(exec_plan)
    prov.update(provenance=kind, mode=exec_plan.mode,
                reasons=list(exec_plan.reasons()),
                inner_kernel=(exec_plan.inner_kernel.to_dict()
                              if exec_plan.inner_kernel is not None else None),
                overlap=exec_plan.overlap)
    report = getattr(plan, "report", None)
    if report is not None:
        prov["predicted_s"] = report.total_time
    if tracer is not None:
        if hasattr(plan, "digest"):
            prov["plan_digest"] = plan.digest()
        prov["calibration_digest"] = getattr(plan, "calibration_digest", "")
    layer, transposed = None, False
    if isinstance(w, StoredWeight):
        from repro.core.gemm import reads_in_place
        as_stored = reads_in_place(exec_plan, ctx.mesh, shape.m, *w.shape,
                                   w.dtype, w.layer is not None,
                                   w.transposed)
        ctx.stats.record_stored(w.layer is not None, w.transposed,
                                as_stored)
        if as_stored:
            w, layer, transposed = w.stored, w.layer, w.transposed
        else:
            w = w.value()
    return dit_gemm(x, w, ctx.mesh, row_axis=ctx.row_axis,
                    col_axis=ctx.col_axis, exec_plan=exec_plan, layer=layer,
                    transposed=transposed)


def pmm(x: jax.Array, w, tag: str = "") -> jax.Array:
    """Plan-routed `x @ w`. x: (..., K); w: (K, N), or a `StoredWeight`
    of that shape -> (..., N).

    Every call, routed or not, emits its ops under
    `jax.named_scope("pmm.<tag>")` (outside the routed `dit_gemm.<mode>`),
    so a device profile attributes each GEMM to its call site."""
    with jax.named_scope(f"pmm.{tag or 'untagged'}"):
        return _pmm(x, w, tag)


def _pmm(x: jax.Array, w: jax.Array, tag: str) -> jax.Array:
    ctx = shard_ctx.get_gemm_context()
    if ctx is None:
        return x @ weight_value(w)
    if not _routable(x, w):
        # not a single dense GEMM this layer understands; stay out of the
        # way — but record it first, or the observed workload silently
        # undercounts whatever the model ran through here
        if (x.ndim >= 1 and w.ndim >= 2 and x.shape[-1] == w.shape[-2]
                and all(int(d) > 0 for d in x.shape)
                and all(int(d) > 0 for d in w.shape)):
            ctx.stats.record(tag, _gemm_shape(x, w))
        ctx.stats.unroutable += 1
        return x @ weight_value(w)
    shape = _gemm_shape(x, w)
    ctx.stats.record(tag, shape)
    tracer = obs_trace.get_tracer()
    if ctx.mesh is None:
        ctx.stats.unrouted += 1
        if tracer is not None:
            tracer.instant(f"pmm.{tag or 'untagged'}", tag=tag,
                           shape=[shape.m, shape.n, shape.k],
                           provenance="unrouted")
            tracer.metrics.counter("pmm.provenance.unrouted").inc()
        return x @ weight_value(w)
    if tracer is None:
        return _dispatch_routed(ctx, x, w, shape, {}, None)
    # spans measure the TRACE-TIME dispatch cost (shapes are static under
    # jit: plan consult + lowering + shard_map tracing happen once per
    # callsite per trace, never per executed step)
    t0 = time.perf_counter()
    with tracer.span(f"pmm.{tag or 'untagged'}", cat=obs_trace.CAT_PMM,
                     tag=tag, shape=[shape.m, shape.n, shape.k]) as prov:
        out = _dispatch_routed(ctx, x, w, shape, prov, tracer)
    dispatch_us = (time.perf_counter() - t0) * 1e6
    tracer.metrics.counter(f"pmm.provenance.{prov['provenance']}").inc()
    tracer.metrics.observe(
        f"pmm.dispatch_us.mode.{prov.get('mode', 'auto')}", dispatch_us)
    tracer.metrics.observe(
        f"pmm.dispatch_us.tag.{tag or 'untagged'}", dispatch_us)
    return out


# ---------------------------------------------------------------------------
# pattn: the attention funnel (pmm's shape, applied to fused attention)
# ---------------------------------------------------------------------------

def _attn_shape(q: jax.Array, k: jax.Array, v: jax.Array,
                causal: bool) -> AttnShape:
    """The attention problem the call solves: q (b, sq, h, d);
    k (b, skv, hkv, d); v (b, skv, hkv, dv)."""
    b, sq, h, d = (int(s) for s in q.shape)
    skv, hkv = int(k.shape[1]), int(k.shape[2])
    return AttnShape(b=b, sq=sq, skv=skv, h=h, hkv=hkv, d=d,
                     dv=int(v.shape[-1]), causal=bool(causal))


def _dispatch_attn(ctx, q, k, v, shape: AttnShape, causal, scale,
                   q_positions, kv_len, unfused, prov: dict, tracer,
                   kv_copy: bool = False):
    """Routed attention dispatch: plan consult -> lower_attention ->
    flat_attention, mirroring `_dispatch_routed` step for step. A shape
    with no plan (or one lowered to `unfused_attn`) executes the caller's
    `unfused` closure — the degrade target is always the named unfused
    path, never a silent mode switch."""
    plan, kind = None, None
    if ctx.planner is not None:
        t0 = time.perf_counter()
        plan, kind = lookup_plan(ctx.planner, shape)
        resolve_us = (time.perf_counter() - t0) * 1e6
        prov["plan_resolve_us"] = round(resolve_us, 1)
        if tracer is not None:
            tracer.metrics.observe("pattn.plan_resolve_us", resolve_us)
        if kind == "hit":
            ctx.stats.hits += 1
        elif kind == "bucketed":
            ctx.stats.bucketed += 1
        elif kind == "analytic":
            ctx.stats.analytic += 1
    if plan is None:
        ctx.stats.fallback += 1
        prov.update(provenance="fallback", mode="unfused_attn",
                    inner_kernel=None, overlap=False)
        return unfused()
    from repro.core.lower import lower_attention
    exec_plan = lower_attention(getattr(plan, "schedule", plan), ctx.mesh,
                                ctx.row_axis, ctx.col_axis, shape=shape,
                                kv_copy=kv_copy)
    ctx.stats.record_lowering(exec_plan)
    prov.update(provenance=kind, mode=exec_plan.mode,
                reasons=list(exec_plan.reasons()),
                inner_kernel=None, overlap=False,
                attn_schedule=getattr(plan, "schedule", plan).describe())
    report = getattr(plan, "report", None)
    if report is not None:
        prov["predicted_s"] = report.total_time
    if tracer is not None:
        if hasattr(plan, "digest"):
            prov["plan_digest"] = plan.digest()
        prov["calibration_digest"] = getattr(plan, "calibration_digest", "")
    if exec_plan.mode == "unfused_attn":
        return unfused()
    from repro.core.attention import flat_attention
    return flat_attention(q, k, v, ctx.mesh, exec_plan, causal=causal,
                          scale=scale, q_positions=q_positions,
                          kv_len=kv_len)


def pattn(q: jax.Array, k: jax.Array, v: jax.Array, *, unfused,
          causal: bool = True, tag: str = "", scale=None,
          q_positions=None, kv_len=None, kv_copy: bool = False) -> jax.Array:
    """Plan-routed attention. q: (b, sq, h, d); k/v: (b, skv, hkv, d|dv) ->
    (b, sq, h, dv). `unfused` is the zero-arg reference path the call
    degrades to when routing is off or the lowering says fused is illegal
    — every degrade is counted and carries a machine-readable reason.
    `kv_copy`: q/k/v are a view built for the fused executor that `unfused`
    does not read (so XLA drops it whenever the call degrades), and on one
    device the call takes `unfused` (`lower_attention`).
    Either path emits its ops under `jax.named_scope("pattn.<tag>")`."""
    with jax.named_scope(f"pattn.{tag or 'untagged'}"):
        return _pattn(q, k, v, unfused, causal, tag, scale, q_positions,
                      kv_len, kv_copy)


def _pattn(q, k, v, unfused, causal, tag, scale, q_positions, kv_len,
           kv_copy):
    ctx = shard_ctx.get_gemm_context()
    if ctx is None:
        return unfused()
    shape = _attn_shape(q, k, v, causal)
    ctx.stats.record_attn(tag, shape)
    tracer = obs_trace.get_tracer()
    if ctx.mesh is None:
        ctx.stats.unrouted += 1
        if tracer is not None:
            tracer.instant(f"pattn.{tag or 'untagged'}", tag=tag,
                           shape=[shape.b, shape.sq, shape.skv, shape.h,
                                  shape.hkv, shape.d, shape.dv],
                           provenance="unrouted")
            tracer.metrics.counter("pattn.provenance.unrouted").inc()
        return unfused()
    if tracer is None:
        return _dispatch_attn(ctx, q, k, v, shape, causal, scale,
                              q_positions, kv_len, unfused, {}, None,
                              kv_copy)
    t0 = time.perf_counter()
    with tracer.span(f"pattn.{tag or 'untagged'}", cat=obs_trace.CAT_PMM,
                     tag=tag, shape=[shape.b, shape.sq, shape.skv, shape.h,
                                     shape.hkv, shape.d, shape.dv]) as prov:
        out = _dispatch_attn(ctx, q, k, v, shape, causal, scale,
                             q_positions, kv_len, unfused, prov, tracer,
                             kv_copy)
    dispatch_us = (time.perf_counter() - t0) * 1e6
    tracer.metrics.counter(f"pattn.provenance.{prov['provenance']}").inc()
    tracer.metrics.observe(
        f"pattn.dispatch_us.mode.{prov.get('mode', 'unfused_attn')}",
        dispatch_us)
    tracer.metrics.observe(
        f"pattn.dispatch_us.tag.{tag or 'untagged'}", dispatch_us)
    return out
