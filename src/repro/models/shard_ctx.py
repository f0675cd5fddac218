"""Launcher-installed execution context for the model stack.

Two layers, both set by launchers before tracing and no-ops when absent:

1. **Activation-sharding context** (`set_mesh` / `constrain_tokens`): pins
   the residual stream's layout. Without explicit constraints XLA's sharding
   propagation may legally trade batch sharding for contraction sharding on
   FSDP weights (each device then computes the FULL batch through a weight
   slice — same matmul FLOPs, but every downstream op replicates over the
   data axis; observed 2-4x compute inflation on the production mesh).
   Pinning `(batch=dp, seq=None, d_model=None)` at every block boundary
   keeps the program in the intended DP x TP regime — this is DiT's
   data-layout control (paper §3.2) applied to activations.

2. **GEMM-routing context** (`set_gemm_context` / `GemmContext`): the mesh
   context extended into a full gemm context. It carries the device mesh plus
   the deployment `Planner` whose warmed plan cache decides how each model
   matmul executes; `repro.models.matmul.pmm` consults it at trace time and
   dispatches through `repro.core.gemm.dit_gemm`. The context also records
   every (tag, GEMMShape) the model actually traces — under a record-only
   context, the workload `repro.models.workload.gemm_workload` warms — and
   keeps routing stats (exact hit / bucketed / analytic online-tune /
   fallback) for the launcher's shutdown report. With no context installed, `pmm` is exactly `x @ w`, so
   smoke tests and meshless tracing are unchanged.

See docs/architecture.md for the full routing path.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, Iterator, List, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_MESH: Optional[Mesh] = None


def set_mesh(mesh: Optional[Mesh]) -> None:
    global _MESH
    _MESH = mesh


def get_mesh() -> Optional[Mesh]:
    return _MESH


# ---------------------------------------------------------------------------
# GEMM-routing context
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class GemmStats:
    """Trace-time routing counters + the observed (tag, shape) workload.

    Counts are per *traced* `pmm` call (shapes are static, so each jit trace
    consults the planner once per callsite per layer group); `observed` maps
    (tag, GEMMShape) -> trace count: the workload a record-only trace
    hands the planner to warm (`repro.models.workload`).
    """
    hits: int = 0          # served a fully-tuned plan
    bucketed: int = 0      # served a bucket-transferred plan
    analytic: int = 0      # served an online-tuned (analytic shortlist) plan
    fallback: int = 0      # no usable plan -> auto dataflow
    unrouted: int = 0      # recorded but not routed (no mesh in the context)
    unroutable: int = 0    # pmm calls that are not a single dense GEMM
    #                        (batched weights etc.) — recorded, never routed
    observed: Dict[Tuple[str, object], int] = dataclasses.field(
        default_factory=dict)
    # attention dispatches (pattn) keep a separate observed map: the GEMM
    # `observed` feeds `observed_shapes`, whose consumers bucket and tune
    # (m, n, k) GEMMs — an AttnShape there would crash them. Keys are
    # (tag, AttnShape).
    attn_observed: Dict[Tuple[str, object], int] = dataclasses.field(
        default_factory=dict)
    # schedule->mesh lowering outcomes (repro.core.lower.ExecPlan): which
    # mode each plan-served matmul actually executed, and the
    # machine-readable reason for every degradation along the way
    modes: Dict[str, int] = dataclasses.field(default_factory=dict)
    degrades: Dict[str, int] = dataclasses.field(default_factory=dict)
    silent_degrades: int = 0   # auto executions with NO recorded reason
    #                            (structurally 0: every ExecPlan fallback
    #                            carries a reason; kept as the cross-check)
    # routed GEMMs handed one layer of a stacked weight (models.matmul.
    # StoredWeight): read where it lies by the kernel, or sliced out first
    # (blocks that do not divide it, a cast, a mesh that reshards it, or
    # XLA's dot, which is handed the slice)
    in_place: int = 0
    sliced: int = 0
    # routed GEMMs handed a weight stored as its transpose (a tied LM
    # head's embedding): read as stored by the kernel, or handed the
    # transposed copy (for the same reasons)
    read_transposed: int = 0
    copied_transposed: int = 0

    def record(self, tag: str, shape, count: int = 1) -> None:
        """`count` > 1 logs one traced call that stands for `count` GEMMs of
        this shape (MLA's absorbed form runs n_heads per-head contractions
        in one einsum)."""
        key = (tag, shape)
        self.observed[key] = self.observed.get(key, 0) + count

    def record_attn(self, tag: str, shape) -> None:
        key = (tag, shape)
        self.attn_observed[key] = self.attn_observed.get(key, 0) + 1

    def record_stored(self, layered: bool, transposed: bool,
                      as_stored: bool) -> None:
        """Count a routed GEMM handed a weight as stored (a layer of a
        stack, a transpose, or both): read so, or copied out."""
        if layered:
            if as_stored:
                self.in_place += 1
            else:
                self.sliced += 1
        if transposed:
            if as_stored:
                self.read_transposed += 1
            else:
                self.copied_transposed += 1

    def record_lowering(self, exec_plan) -> None:
        """Count an ExecPlan's executed mode + its fallback-chain reasons."""
        self.modes[exec_plan.mode] = self.modes.get(exec_plan.mode, 0) + 1
        for fb in exec_plan.fallbacks:
            self.degrades[fb.reason] = self.degrades.get(fb.reason, 0) + 1
        if exec_plan.mode == "auto" and not exec_plan.fallbacks:
            self.silent_degrades += 1

    @property
    def routed(self) -> int:
        return self.hits + self.bucketed + self.analytic + self.fallback

    @property
    def resolved(self) -> int:
        """Calls that found a plan — cached, bucketed, or online-tuned
        (the hit-rate numerator)."""
        return self.hits + self.bucketed + self.analytic

    @property
    def resolve_rate(self) -> float:
        return self.resolved / self.routed if self.routed else 0.0

    def observed_shapes(self) -> List[object]:
        """Deduplicated GEMMShapes the model actually traced."""
        return list(dict.fromkeys(shape for (_, shape) in self.observed))

    def to_dict(self) -> Dict[str, object]:
        """JSON-able snapshot — the run report's `routing` section.

        Counters plus derived summaries (`calls`, `routed`, `resolve_rate`)
        so report consumers never recompute them, and the observed
        (tag, shape) workload as a stable list. `from_dict` round-trips it.
        """
        return {
            "calls": self.routed + self.unrouted,
            "routed": self.routed,
            "hits": self.hits,
            "bucketed": self.bucketed,
            "analytic": self.analytic,
            "fallback": self.fallback,
            "unrouted": self.unrouted,
            "unroutable": self.unroutable,
            "resolve_rate": self.resolve_rate,
            "modes": dict(sorted(self.modes.items())),
            "degrades": dict(sorted(self.degrades.items())),
            "silent_degrades": self.silent_degrades,
            "in_place": self.in_place,
            "sliced": self.sliced,
            "read_transposed": self.read_transposed,
            "copied_transposed": self.copied_transposed,
            "observed": [
                {"tag": tag,
                 "shape": ([int(s.m), int(s.n), int(s.k)]
                           if hasattr(s, "m") else list(s)),
                 "count": count}
                for (tag, s), count in self.observed.items()],
            "attn_observed": [
                {"tag": tag,
                 "shape": [int(s.b), int(s.sq), int(s.skv), int(s.h),
                           int(s.hkv), int(s.d), int(s.dv),
                           int(s.causal)],
                 "count": count}
                for (tag, s), count in self.attn_observed.items()],
        }

    @classmethod
    def from_dict(cls, d: Dict[str, object]) -> "GemmStats":
        """Rebuild a stats object from `to_dict()` output (derived fields
        like `calls`/`routed`/`resolve_rate` are recomputed, not read)."""
        from repro.core.schedule import AttnShape, GEMMShape
        stats = cls(hits=int(d["hits"]), bucketed=int(d["bucketed"]),
                    analytic=int(d.get("analytic", 0)),
                    fallback=int(d["fallback"]), unrouted=int(d["unrouted"]),
                    unroutable=int(d.get("unroutable", 0)),
                    modes=dict(d.get("modes", {})),
                    degrades=dict(d.get("degrades", {})),
                    silent_degrades=int(d.get("silent_degrades", 0)),
                    in_place=int(d.get("in_place", 0)),
                    sliced=int(d.get("sliced", 0)),
                    read_transposed=int(d.get("read_transposed", 0)),
                    copied_transposed=int(d.get("copied_transposed", 0)))
        for rec in d.get("observed", []):
            key = (rec["tag"], GEMMShape(*rec["shape"]))
            stats.observed[key] = int(rec["count"])
        for rec in d.get("attn_observed", []):
            b, sq, skv, h, hkv, dd, dv, causal = rec["shape"]
            key = (rec["tag"], AttnShape(b, sq, skv, h, hkv, dd, dv,
                                         bool(causal)))
            stats.attn_observed[key] = int(rec["count"])
        return stats

    def describe(self) -> str:
        # render from the dict so the print and the run report cannot drift
        from repro.obs.report import describe_routing
        return describe_routing(self.to_dict())


@dataclasses.dataclass
class GemmContext:
    """What `pmm` needs to route a model matmul through `dit_gemm`.

    mesh=None makes the context record-only: every pmm call is logged in
    `stats.observed` but executes as plain `x @ w` (used by dry-runs and
    `models.workload.gemm_workload`, which trace without devices to spare).
    """
    mesh: Optional[Mesh] = None
    planner: Optional[object] = None      # repro.deploy.Planner (duck-typed)
    row_axis: str = "data"
    col_axis: str = "model"
    stats: GemmStats = dataclasses.field(default_factory=GemmStats)


_GEMM_CTX: Optional[GemmContext] = None


def set_gemm_context(ctx: Optional[GemmContext]) -> None:
    global _GEMM_CTX
    _GEMM_CTX = ctx


def get_gemm_context() -> Optional[GemmContext]:
    return _GEMM_CTX


@contextlib.contextmanager
def gemm_context(ctx: GemmContext) -> Iterator[GemmContext]:
    """Scoped install (tests); launchers use set_gemm_context directly."""
    prev = _GEMM_CTX
    set_gemm_context(ctx)
    try:
        yield ctx
    finally:
        set_gemm_context(prev)


def _dp(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def constrain_tokens(x: jax.Array) -> jax.Array:
    """(B, S, D) or (B, S): batch over the DP axes when it divides."""
    if _MESH is None:
        return x
    dp = _dp(_MESH)
    size = 1
    for a in dp:
        size *= _MESH.shape[a]
    if x.shape[0] % size:
        return x
    spec = P(dp, *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, NamedSharding(_MESH, spec))
