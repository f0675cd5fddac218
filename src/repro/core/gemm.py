"""Distributed `dit_gemm` — the paper's dataflow pattern primitives retargeted
to a JAX device mesh (DESIGN.md §2.2 table).

SoftHier's tile grid becomes the named mesh; its hardware NoC collectives
become `jax.lax` collectives inside `shard_map`:

- **summa** (Fig. 6a): K-panel loop; each step one-hot-psum-broadcasts the A
  panel along the column axis and the B panel along the row axis (a psum of a
  masked operand IS a fabric broadcast from the owner — the mask-based
  multicast of §2.1), then accumulates the local C block.
- **cannon** (Fig. 6b systolic): Cannon's algorithm — initial skew, then
  rotate A west / B north with `ppermute` (nearest-neighbour ICI hops) and
  accumulate. Square meshes.
- **splitk** (Fig. 6e, 1-D): K sharded; local partial GEMM then `psum_scatter`
  (reduction ownership round-robined over the k-group — §3.1.1's reduction
  policy; `psum` keeps a replicated C = the 'first'-owner policy analogue).
- **splitk_summa** (Fig. 6e, 3-D): the schedule's gk k-groups each run SUMMA
  over a (row × col) sub-grid on their K slice, then the partials NoC-reduce
  over a dedicated k sub-axis of the mesh — the tuned (gm × gn × gk) logical
  grid mapped onto a mesh view instead of collapsing to 1-D split-K.
- **hierarchical** (Fig. 6d, SUMMA over systolic): outer SUMMA over inner
  Cannon groups — each physical axis splits into (outer, inner) per
  `Schedule.inner`; owner groups psum-broadcast outer K-panels along the
  outer axes while each inner group contracts its panel systolically.
- **outer_systolic** (Fig. 6c, systolic over SUMMA): the dual composition —
  an outer Cannon ring of inner SUMMA groups. A/B chunks propagate between
  whole tile groups as a global wavefront (`ppermute` ring steps over the
  outer axes, wavefront skew by outer grid index) while each inner group
  runs the shared `_summa_acc` body on its subproblem.
- **allgather** (beyond-paper baseline): gather all panels once, single local
  GEMM. Highest memory, fewest collectives — XLA's default TP pattern.
- **auto**: sharding-constrained einsum; XLA chooses the collective schedule.

Schedule-driven dispatch goes through `repro.core.lower.lower_schedule`,
which resolves the tuned dataflow + logical grid into an `ExecPlan` (mode,
mesh view, kwargs, explicit fallback chain); `dit_gemm` consumes the plan.

A weight may come as it is stored: a layer loop's as its stack and the
layer's index (`layer=`), a tied LM head's as the embedding (N, K), its
transpose (`transposed=`). Where one device holds it whole and the plan's
kernel can read it so (`reads_in_place`), the mode's one-device body is
that kernel alone; the caller copies the (K, N) matrix out otherwise.

All modes are numerically validated against each other on a multi-device CPU
mesh (tests/test_gemm_modes.py, tests/test_lowering.py; subprocess with fake
devices). The panel / skew / rotate loops are `lax.scan` (not `fori_loop`)
so every mode is reverse-differentiable — plan-routed training matmuls
backprop through the collectives.

See docs/dataflows.md for the mode-by-mode collective patterns, divisibility
preconditions, and fallback reasons.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.lower import ExecPlan, lower_schedule
from repro.core.schedule import InnerKernel

# modes dispatchable by name; the plan-only modes (splitk_summa,
# hierarchical, outer_systolic) additionally need a mesh view — see
# lower.EXEC_MODES.
MODES = ("auto", "summa", "cannon", "splitk", "allgather")


def _tile_dot(a: jax.Array, b: jax.Array,
              kernel: Optional[InnerKernel]) -> jax.Array:
    """The per-device contraction every mode body accumulates with.

    `kernel=None` is the legacy path — a bare `jnp.dot` whose inner schedule
    XLA picks. With a plan-resolved `InnerKernel` the contraction routes
    through `kernels.ops.local_matmul` at the planner's block geometry /
    compute dtype (Pallas on TPU, the bitwise-identical jnp oracle on CPU),
    making the intra-device level a tuned schedule dimension rather than a
    compiler default. fp32 out either way.
    """
    if kernel is None:
        return jnp.dot(a, b, preferred_element_type=jnp.float32)
    from repro.kernels.ops import local_matmul
    return local_matmul(a, b, kernel)


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name]


def _mode_scope(mode: str):
    """Name the HLO ops every mode's dispatch traces (`jax.named_scope`):
    a device profile segments by `dit_gemm.<mode>`. The host-side
    trace-time work is `pmm`'s span (`pmm.<tag>`)."""
    return jax.named_scope(f"dit_gemm.{mode}")


# ---------------------------------------------------------------------------
# SUMMA
# ---------------------------------------------------------------------------

def _summa_acc(a_loc: jax.Array, b_loc: jax.Array, row_axis: str,
               col_axis: str, dm: int, dn: int,
               kernel: Optional[InnerKernel] = None) -> jax.Array:
    """fp32 SUMMA accumulation of the local C block over dm*dn K-panels.

    Runs inside shard_map over (row_axis, col_axis) — which may be sub-axes
    of a larger mesh view, in which case the broadcasts stay within the
    enclosing group (the k-group of splitk_summa).
    """
    panels = dm * dn
    w = a_loc.shape[1] // dm
    i = jax.lax.axis_index(row_axis)
    j = jax.lax.axis_index(col_axis)

    def step(acc, p):
        # A panel p lives on column p // dm at local offset (p % dm) * w
        a_pan = jax.lax.dynamic_slice_in_dim(a_loc, (p % dm) * w, w, axis=1)
        a_pan = jnp.where(j == p // dm, a_pan, jnp.zeros_like(a_pan))
        a_pan = jax.lax.psum(a_pan, col_axis)          # owner broadcast
        # B panel p lives on row p // dn at local offset (p % dn) * w
        b_pan = jax.lax.dynamic_slice_in_dim(b_loc, (p % dn) * w, w, axis=0)
        b_pan = jnp.where(i == p // dn, b_pan, jnp.zeros_like(b_pan))
        b_pan = jax.lax.psum(b_pan, row_axis)          # owner broadcast
        acc = acc + _tile_dot(a_pan, b_pan, kernel)
        return acc, None

    acc = jnp.zeros((a_loc.shape[0], b_loc.shape[1]), dtype=jnp.float32)
    acc, _ = jax.lax.scan(step, acc, jnp.arange(panels))
    return acc


def summa_gemm(a: jax.Array, b: jax.Array, mesh: Mesh,
               row_axis: str = "data", col_axis: str = "model",
               kernel: Optional[InnerKernel] = None) -> jax.Array:
    """C[i,j] = sum_p A_panel[i,p] @ B_panel[p,j] with owner broadcasts.

    A is sharded (row_axis, col_axis), B (row_axis, col_axis), C likewise.
    K is split into dm*dn panels so both operands agree on panel width.
    """
    dm, dn = _axis_size(mesh, row_axis), _axis_size(mesh, col_axis)
    m, k = a.shape
    _, n = b.shape
    panels = dm * dn
    if k % panels:
        raise ValueError(f"K={k} must divide by {panels} SUMMA panels")

    def body(a_loc, b_loc):
        return _summa_acc(a_loc, b_loc, row_axis, col_axis,
                          dm, dn, kernel).astype(a_loc.dtype)

    spec2 = P(row_axis, col_axis)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec2, spec2),
                         out_specs=spec2, check_vma=False)(a, b)


# ---------------------------------------------------------------------------
# Cannon (systolic)
# ---------------------------------------------------------------------------

def _cannon_acc(a_blk: jax.Array, b_blk: jax.Array, row_axis: str,
                col_axis: str, d: int,
                kernel: Optional[InnerKernel] = None,
                overlap: bool = False) -> jax.Array:
    """fp32 Cannon accumulation on a square d x d (sub-)grid: initial skew,
    then d rotate-and-accumulate steps over `ppermute` rings.

    Like `_summa_acc`, the axes may be inner sub-axes of a mesh view — the
    wavefront then stays within each inner group (hierarchical mode).

    `overlap=True` issues step s+1's ring hops BEFORE consuming step s's
    blocks inside each scan step — numerically identical (the dot still
    reads the pre-rotation blocks), but the collectives are no longer
    data-dependent successors of the contraction, so XLA's async collective
    machinery can hide each `ppermute` behind the tile compute (the paper's
    §3.3.1 DMA/compute double-buffering, at the mesh level).
    """
    left = [(s, (s - 1) % d) for s in range(d)]          # shift along cols
    up = [(s, (s - 1) % d) for s in range(d)]            # shift along rows
    i = jax.lax.axis_index(row_axis)
    j = jax.lax.axis_index(col_axis)

    # initial skew: A block (i, j) -> (i, j - i); B block (i, j) -> (i - j, j).
    # every device executes the same d-1 uniform ppermutes (SPMD-safe)
    # and masks acceptance by its row/column index.
    def skew_a(val, s):
        shifted = jax.lax.ppermute(val, col_axis, left)
        return jnp.where(i > s, shifted, val), None

    def skew_b(val, s):
        shifted = jax.lax.ppermute(val, row_axis, up)
        return jnp.where(j > s, shifted, val), None

    with jax.named_scope("skew"):
        a_cur, _ = jax.lax.scan(skew_a, a_blk, jnp.arange(d - 1))
        b_cur, _ = jax.lax.scan(skew_b, b_blk, jnp.arange(d - 1))

    def step(carry, _):
        a_cur, b_cur, acc = carry
        if overlap:
            # issue next step's hops first; consume the held blocks after
            a_nxt = jax.lax.ppermute(a_cur, col_axis, left)
            b_nxt = jax.lax.ppermute(b_cur, row_axis, up)
            acc = acc + _tile_dot(a_cur, b_cur, kernel)
            return (a_nxt, b_nxt, acc), None
        acc = acc + _tile_dot(a_cur, b_cur, kernel)
        a_cur = jax.lax.ppermute(a_cur, col_axis, left)
        b_cur = jax.lax.ppermute(b_cur, row_axis, up)
        return (a_cur, b_cur, acc), None

    acc = jnp.zeros((a_blk.shape[0], b_blk.shape[1]), dtype=jnp.float32)
    with jax.named_scope("rotate_accumulate"):
        (_, _, acc), _ = jax.lax.scan(step, (a_cur, b_cur, acc), None,
                                      length=d)
    return acc


def cannon_gemm(a: jax.Array, b: jax.Array, mesh: Mesh,
                row_axis: str = "data", col_axis: str = "model",
                kernel: Optional[InnerKernel] = None,
                overlap: bool = False) -> jax.Array:
    """Systolic GEMM on a square mesh: skew, then rotate-and-accumulate.

    Every transfer is a single nearest-neighbour hop (`ppermute` ring) — the
    wavefront dataflow of Fig. 6b on the ICI torus.
    """
    dm, dn = _axis_size(mesh, row_axis), _axis_size(mesh, col_axis)
    if dm != dn:
        raise ValueError(f"cannon needs a square mesh, got {dm}x{dn}")

    def body(a_loc, b_loc):
        return _cannon_acc(a_loc, b_loc, row_axis, col_axis,
                           dm, kernel, overlap).astype(a_loc.dtype)

    spec2 = P(row_axis, col_axis)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec2, spec2),
                         out_specs=spec2, check_vma=False)(a, b)


# ---------------------------------------------------------------------------
# Split-K (1-D and the schedule's 3-D grid)
# ---------------------------------------------------------------------------

def splitk_gemm(a: jax.Array, b: jax.Array, mesh: Mesh,
                k_axis: str = "model", scatter: bool = True,
                kernel: Optional[InnerKernel] = None) -> jax.Array:
    """K sharded over `k_axis`; local partial GEMM + NoC reduction.

    scatter=True  -> psum_scatter: C row-blocks round-robined over the k-group
                     (the paper's round_robin reduction-owner policy).
    scatter=False -> psum: replicated C (every k-peer ends with the result).
    """
    dk = _axis_size(mesh, k_axis)
    m = a.shape[0]
    if scatter and m % dk:
        raise ValueError(f"M={m} must divide by k-axis size {dk} for scatter")

    def body(a_loc, b_loc):
        part = _tile_dot(a_loc, b_loc, kernel)
        if scatter:
            out = jax.lax.psum_scatter(part, k_axis, scatter_dimension=0,
                                       tiled=True)
        else:
            out = jax.lax.psum(part, k_axis)
        return out.astype(a_loc.dtype)

    in_specs = (P(None, k_axis), P(k_axis, None))
    out_specs = P(k_axis, None) if scatter else P(None, None)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(a, b)


def splitk_summa_gemm(a: jax.Array, b: jax.Array, mesh: Mesh,
                      row_axis: str = "data", col_axis: str = "model",
                      k_axis: str = "splitk",
                      scatter: bool = True,
                      kernel: Optional[InnerKernel] = None) -> jax.Array:
    """3-D split-K on a (row × col × k) mesh view: each of the gk k-groups
    runs SUMMA over its (row × col) sub-grid on a K/gk slice, then partials
    reduce over the k sub-axis.

    A is sharded (m: row, k: k-major/col-minor), B (k: k-major/row-minor,
    n: col) — each k-group holds a contiguous K slice laid out exactly as
    plain SUMMA expects. scatter=True round-robins C row-blocks over the
    k-group (out spec P((row, k), col)); scatter=False psums to a C
    replicated over k.
    """
    rm, rn = _axis_size(mesh, row_axis), _axis_size(mesh, col_axis)
    gk = _axis_size(mesh, k_axis)
    m, k = a.shape
    if k % (gk * rm * rn):
        raise ValueError(f"K={k} must divide by gk*rm*rn={gk * rm * rn}")
    if scatter and m % (rm * gk):
        raise ValueError(f"M={m} must divide by rm*gk={rm * gk} for scatter")

    def body(a_loc, b_loc):
        acc = _summa_acc(a_loc, b_loc, row_axis, col_axis, rm, rn, kernel)
        if scatter:
            out = jax.lax.psum_scatter(acc, k_axis, scatter_dimension=0,
                                       tiled=True)
        else:
            out = jax.lax.psum(acc, k_axis)
        return out.astype(a_loc.dtype)

    in_specs = (P(row_axis, (k_axis, col_axis)), P((k_axis, row_axis), col_axis))
    out_specs = (P((row_axis, k_axis), col_axis) if scatter
                 else P(row_axis, col_axis))
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(a, b)


# ---------------------------------------------------------------------------
# Hierarchical: outer SUMMA over inner Cannon groups
# ---------------------------------------------------------------------------

def hierarchical_gemm(a: jax.Array, b: jax.Array, mesh: Mesh,
                      row_axis: str = "data", col_axis: str = "model",
                      inner_row: str = "data_in",
                      inner_col: str = "model_in",
                      kernel: Optional[InnerKernel] = None,
                      overlap: bool = False) -> jax.Array:
    """Hierarchical dataflow on an (outer_row × inner_row × outer_col ×
    inner_col) mesh view — the mesh analogue of the paper's Fig. 6d
    (SUMMA over systolic): the outer (Om × On) grid of inner (ih × ih)
    groups runs SUMMA at the group level while each group contracts its
    K-panel with Cannon's wavefront. Fig. 6c's dual composition is
    `outer_systolic_gemm` below.

    Per outer panel p (of Om*On): the owner outer-column psum-broadcasts the
    A panel along `col_axis`, the owner outer-row the B panel along
    `row_axis` (masked-psum = the mask-based multicast of §2.1, here between
    whole tile groups); each device slices its Cannon block from the
    group-gathered panel, and the inner group accumulates it systolically.
    """
    om, ih = _axis_size(mesh, row_axis), _axis_size(mesh, inner_row)
    on, iw = _axis_size(mesh, col_axis), _axis_size(mesh, inner_col)
    if ih != iw:
        raise ValueError(f"hierarchical needs square inner groups, got {ih}x{iw}")
    m, k = a.shape
    _, n = b.shape
    if k % (om * on * ih):
        raise ValueError(f"K={k} must divide by Om*On*ih={om * on * ih}")
    wo = k // (om * on)          # outer K-panel width (per group)
    wk = wo // ih                # inner Cannon block width
    panels = om * on

    def body(a_loc, b_loc):
        oi = jax.lax.axis_index(row_axis)
        oj = jax.lax.axis_index(col_axis)
        li = jax.lax.axis_index(inner_row)
        lj = jax.lax.axis_index(inner_col)
        # reassemble each group's contiguous K range so any outer panel can
        # be sliced uniformly (alignment-free at the cost of one gather)
        a_g = jax.lax.all_gather(a_loc, inner_col, axis=1, tiled=True)
        b_g = jax.lax.all_gather(b_loc, inner_row, axis=0, tiled=True)

        def outer_step(acc, p):
            # A panel p: owner outer-col p // om, group-local offset
            # (p % om) * wo; this device's Cannon block is k sub-chunk lj
            a_pan = jax.lax.dynamic_slice_in_dim(
                a_g, (p % om) * wo + lj * wk, wk, axis=1)
            a_pan = jnp.where(oj == p // om, a_pan, jnp.zeros_like(a_pan))
            a_pan = jax.lax.psum(a_pan, col_axis)       # group broadcast
            # B panel p: owner outer-row p // on; Cannon block = sub-chunk li
            b_pan = jax.lax.dynamic_slice_in_dim(
                b_g, (p % on) * wo + li * wk, wk, axis=0)
            b_pan = jnp.where(oi == p // on, b_pan, jnp.zeros_like(b_pan))
            b_pan = jax.lax.psum(b_pan, row_axis)       # group broadcast
            acc = acc + _cannon_acc(a_pan, b_pan, inner_row, inner_col, ih,
                                    kernel, overlap)
            return acc, None

        acc = jnp.zeros((a_loc.shape[0], b_loc.shape[1]), dtype=jnp.float32)
        with jax.named_scope("outer_panels"):
            acc, _ = jax.lax.scan(outer_step, acc, jnp.arange(panels))
        return acc.astype(a_loc.dtype)

    spec = P((row_axis, inner_row), (col_axis, inner_col))
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec), out_specs=spec,
                         check_vma=False)(a, b)


# ---------------------------------------------------------------------------
# Outer-systolic: outer Cannon ring of inner SUMMA groups (Fig. 6c)
# ---------------------------------------------------------------------------

def outer_systolic_gemm(a: jax.Array, b: jax.Array, mesh: Mesh,
                        row_axis: str = "data", col_axis: str = "model",
                        inner_row: str = "data_in",
                        inner_col: str = "model_in",
                        kernel: Optional[InnerKernel] = None,
                        overlap: bool = False) -> jax.Array:
    """Fig. 6c's systolic-over-SUMMA composition on an (outer_row ×
    inner_row × outer_col × inner_col) mesh view: Cannon's wavefront runs at
    the *group* level while each inner (ih × ih) group contracts its current
    K-chunk with SUMMA.

    K splits into D = Om (== On) outer chunks, one per group column. After
    the initial group-level skew (A group-block (oi, oj) → (oi, oj − oi);
    B → (oi − oj, oj)), every outer step contracts the held chunk through
    the shared `_summa_acc` body inside the group, then rotates the whole
    A chunk one group west and the B chunk one group north — each rotation
    is a single `ppermute` ring step over an *outer* axis, so A/B chunks
    propagate between tile groups as a global wavefront (the paper's
    group-to-group P2P of the hold buffers) with no broadcast at the outer
    level at all.

    Needs a square outer grid (the ring) and square inner groups (the inner
    SUMMA panel algebra): `lower_schedule` falls back to `hierarchical`
    otherwise, with the reason recorded.
    """
    om, ih = _axis_size(mesh, row_axis), _axis_size(mesh, inner_row)
    on, iw = _axis_size(mesh, col_axis), _axis_size(mesh, inner_col)
    if ih != iw:
        raise ValueError(f"outer_systolic needs square inner groups, "
                         f"got {ih}x{iw}")
    if om != on:
        raise ValueError(f"outer_systolic needs a square outer grid, "
                         f"got {om}x{on}")
    m, k = a.shape
    if k % (om * ih * ih):
        raise ValueError(f"K={k} must divide by Om*ih^2={om * ih * ih}")
    d = om

    def body(a_loc, b_loc):
        oi = jax.lax.axis_index(row_axis)
        oj = jax.lax.axis_index(col_axis)
        ring = [(s, (s - 1) % d) for s in range(d)]

        # group-level skew: like `_cannon_acc`'s, but masked by the OUTER
        # grid index — every device in outer row oi applies oi ring hops
        def skew_a(val, s):
            shifted = jax.lax.ppermute(val, col_axis, ring)
            return jnp.where(oi > s, shifted, val), None

        def skew_b(val, s):
            shifted = jax.lax.ppermute(val, row_axis, ring)
            return jnp.where(oj > s, shifted, val), None

        with jax.named_scope("outer_skew"):
            a_cur, _ = jax.lax.scan(skew_a, a_loc, jnp.arange(d - 1))
            b_cur, _ = jax.lax.scan(skew_b, b_loc, jnp.arange(d - 1))

        def outer_step(carry, _):
            a_cur, b_cur, acc = carry
            if overlap:
                # next chunk's group-to-group hops issue before this chunk
                # is consumed — the outer ring hides behind inner compute
                a_nxt = jax.lax.ppermute(a_cur, col_axis, ring)
                b_nxt = jax.lax.ppermute(b_cur, row_axis, ring)
                acc = acc + _summa_acc(a_cur, b_cur, inner_row, inner_col,
                                       ih, ih, kernel)
                return (a_nxt, b_nxt, acc), None
            acc = acc + _summa_acc(a_cur, b_cur, inner_row, inner_col,
                                   ih, ih, kernel)
            a_cur = jax.lax.ppermute(a_cur, col_axis, ring)   # chunk west
            b_cur = jax.lax.ppermute(b_cur, row_axis, ring)   # chunk north
            return (a_cur, b_cur, acc), None

        acc = jnp.zeros((a_loc.shape[0], b_loc.shape[1]), dtype=jnp.float32)
        with jax.named_scope("outer_steps"):
            (_, _, acc), _ = jax.lax.scan(outer_step, (a_cur, b_cur, acc),
                                          None, length=d)
        return acc.astype(a_loc.dtype)

    spec = P((row_axis, inner_row), (col_axis, inner_col))
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec), out_specs=spec,
                         check_vma=False)(a, b)


# ---------------------------------------------------------------------------
# All-gather baseline + auto
# ---------------------------------------------------------------------------

def allgather_gemm(a: jax.Array, b: jax.Array, mesh: Mesh,
                   row_axis: str = "data", col_axis: str = "model",
                   kernel: Optional[InnerKernel] = None) -> jax.Array:
    """Gather A's panels along cols / B's along rows once, then one local GEMM."""
    def body(a_loc, b_loc):
        a_full = jax.lax.all_gather(a_loc, col_axis, axis=1, tiled=True)
        b_full = jax.lax.all_gather(b_loc, row_axis, axis=0, tiled=True)
        return _tile_dot(a_full, b_full, kernel).astype(a_loc.dtype)

    spec2 = P(row_axis, col_axis)
    return jax.shard_map(body, mesh=mesh, in_specs=(spec2, spec2),
                         out_specs=spec2, check_vma=False)(a, b)


def auto_gemm(a: jax.Array, b: jax.Array, mesh: Mesh,
              row_axis: str = "data", col_axis: str = "model") -> jax.Array:
    """Sharding-constrained einsum: DiT picks the layout (split scheme), XLA
    picks the collective schedule."""
    spec2 = P(row_axis, col_axis)
    a = jax.lax.with_sharding_constraint(a, NamedSharding(mesh, spec2))
    b = jax.lax.with_sharding_constraint(b, NamedSharding(mesh, spec2))
    out = jnp.einsum("mk,kn->mn", a, b, preferred_element_type=jnp.float32)
    out = jax.lax.with_sharding_constraint(out, NamedSharding(mesh, spec2))
    return out.astype(a.dtype)


# ---------------------------------------------------------------------------
# ExecPlan dispatch
# ---------------------------------------------------------------------------

# The most row blocks a kernel may have and still read a stacked layer in
# place: each row block reads all of B from HBM, where a slice is staged
# (in VMEM, where it fits) once. On v5e (chip runs) reading in place won
# at 4 row blocks (phi4-mini's and DeepSeek-V2's prefill chunks, 9-11%
# more tokens/s) and lost at 12 (olmo-1b's, 1.9% later first tokens).
MAX_LAYER_READS = 4
# The most row blocks a kernel may have and still read a weight stored as
# its transpose (a tied LM head's embedding) with no transposed copy: one.
# On v5e (chip runs) a tied head read so
# took 1.70 ms against 9.02 for the transposed, padded copy and the kernel
# at phi4-mini's decode (16x200064x3072, one row block), and lost where the
# GEMM is compute-bound: 51.0 against 42.7 ms at its 2,048-row prefill (4
# row blocks), 21.5 against 15.8 ms at olmo-1b's 6,144 rows (12).
MAX_TRANSPOSED_READS = 1


def reads_in_place(exec_plan: ExecPlan, mesh: Mesh, m: int, k: int,
                   n: int, dtype, stacked: bool = True,
                   transposed: bool = False) -> bool:
    """Whether the plan's (M, K, N) GEMM reads its weight as it is stored:
    one device holds it whole (no mode reshards it there), and the plan's
    Pallas kernel can read it so (`kernels.ops.reads_in_place`). A layer
    of a stack (`stacked`) is read so only where the kernel's row blocks
    read it at most `MAX_LAYER_READS` times, a transpose (`transposed`) at
    most `MAX_TRANSPOSED_READS` times. Otherwise the GEMM is handed the
    (K, N) matrix copied out; XLA's `dot` (no inner kernel) is handed that
    copy too, which it may fuse."""
    from repro.kernels.ops import reads_in_place as kernel_reads
    ik = exec_plan.inner_kernel
    if mesh.devices.size != 1 or ik is None:
        return False
    reads = -(-m // ik.bm)
    return ((not stacked or reads <= MAX_LAYER_READS)
            and (not transposed or reads <= MAX_TRANSPOSED_READS)
            and kernel_reads(ik, k, n, dtype, transposed))


def exec_plan_gemm(a: jax.Array, b: jax.Array, mesh: Mesh,
                   exec_plan: ExecPlan, layer=None,
                   transposed: bool = False) -> jax.Array:
    """Run one 2-D GEMM exactly as a resolved `ExecPlan` prescribes. With
    `layer`, `b` is a stack (L, K, N) and the GEMM reads b[layer] in place;
    with `transposed`, `b` is stored as B's transpose (N, K) and read so;
    either where `reads_in_place` holds."""
    ax = exec_plan.axes
    emesh = (exec_plan.view.materialize(mesh) if exec_plan.view is not None
             else mesh)
    mode = exec_plan.mode
    ik = exec_plan.inner_kernel
    ov = exec_plan.overlap
    if layer is not None or transposed:
        k, n = b.shape[-2:]
        if transposed:
            k, n = n, k
        if not reads_in_place(exec_plan, mesh, a.shape[0], k, n, b.dtype,
                              layer is not None, transposed):
            raise ValueError(f"{exec_plan.describe()} cannot read a "
                             f"{b.shape} weight as stored")
        from repro.kernels.ops import local_matmul
        with _mode_scope(mode):
            # every mode's body on one device is its local product
            return local_matmul(a, b, ik, layer=layer,
                                transposed=transposed).astype(a.dtype)
    with _mode_scope(mode):
        if mode == "auto":
            return auto_gemm(a, b, mesh, ax["row"], ax["col"])
        if mode == "summa":
            return summa_gemm(a, b, emesh, ax["row"], ax["col"], kernel=ik)
        if mode == "cannon":
            return cannon_gemm(a, b, emesh, ax["row"], ax["col"],
                               kernel=ik, overlap=ov)
        if mode == "allgather":
            return allgather_gemm(a, b, emesh, ax["row"], ax["col"],
                                  kernel=ik)
        if mode == "splitk":
            return splitk_gemm(a, b, emesh, k_axis=ax["k"],
                               scatter=exec_plan.kwargs.get("scatter", True),
                               kernel=ik)
        if mode == "splitk_summa":
            return splitk_summa_gemm(
                a, b, emesh, ax["row"], ax["col"], ax["k"],
                scatter=exec_plan.kwargs.get("scatter", True), kernel=ik)
        if mode == "hierarchical":
            return hierarchical_gemm(a, b, emesh, ax["row"], ax["col"],
                                     ax["inner_row"], ax["inner_col"],
                                     kernel=ik, overlap=ov)
        if mode == "outer_systolic":
            return outer_systolic_gemm(a, b, emesh, ax["row"], ax["col"],
                                       ax["inner_row"], ax["inner_col"],
                                       kernel=ik, overlap=ov)
    raise KeyError(f"ExecPlan resolved to unknown mode {mode!r}")


def dit_gemm(a: jax.Array, b: jax.Array, mesh: Mesh, mode: str = "auto",
             row_axis: str = "data", col_axis: str = "model",
             plan=None, planner=None, exec_plan: Optional[ExecPlan] = None,
             layer=None, transposed: bool = False, **kw) -> jax.Array:
    """Dispatch on the deployment schedule's dataflow pattern.

    Three override layers, strongest first:

    - `exec_plan` (a `repro.core.lower.ExecPlan`): a pre-resolved lowering —
      dispatched verbatim (this is how `models.matmul.pmm` calls after
      recording the plan's fallback chain in its stats).
    - `plan` (a `repro.deploy.DeploymentPlan` or a bare `Schedule`) or
      `planner` (a `repro.deploy.Planner`, consulted — and warmed — per
      shape): the tuned schedule is lowered here via `lower_schedule`
      against the actual operand shapes; caller `**kw` dispatch knobs
      (currently `scatter`) merge into the mode kwargs *before* legality,
      so validation sees exactly what dispatch will use — geometry knobs
      are the schedule's alone.
    - `mode` + `**kw`: direct dispatch of one of `MODES`.

    `a` may carry leading batch/seq dims (B, S, K): they flatten into M for
    both the planner's GEMMShape and the shard_map dispatch, and the result
    is reshaped back to (B, S, N). `b` must be 2-D (K, N), or with
    `exec_plan` a weight as stored that the plan reads so
    (`exec_plan_gemm`): with `layer` a stack (L, K, N) and the layer's
    index, with `transposed` B's transpose (N, K).
    """
    if b.ndim != (2 if layer is None else 3) or (
            (layer is not None or transposed) and exec_plan is None):
        raise ValueError(f"dit_gemm expects a 2-D weight, or one as stored "
                         f"with an exec_plan; got {b.shape}")
    lead = a.shape[:-1]
    if a.ndim != 2:
        a = a.reshape(-1, a.shape[-1])
    if planner is not None and plan is None:
        from repro.core.schedule import GEMMShape
        plan = planner.plan(GEMMShape(a.shape[0], b.shape[1], a.shape[1]))
    if exec_plan is None and plan is not None:
        sched = getattr(plan, "schedule", plan)
        exec_plan = lower_schedule(sched, mesh, row_axis, col_axis,
                                   shape=(a.shape[0], b.shape[1], a.shape[1]),
                                   overrides=kw)
    if exec_plan is not None:
        out = exec_plan_gemm(a, b, mesh, exec_plan, layer, transposed)
    elif mode not in MODES:
        raise KeyError(f"unknown mode {mode!r}; have {MODES}")
    else:
        with _mode_scope(mode):
            if mode == "auto":
                out = auto_gemm(a, b, mesh, row_axis, col_axis)
            elif mode == "summa":
                out = summa_gemm(a, b, mesh, row_axis, col_axis)
            elif mode == "cannon":
                out = cannon_gemm(a, b, mesh, row_axis, col_axis)
            elif mode == "splitk":
                out = splitk_gemm(a, b, mesh,
                                  k_axis=kw.get("k_axis", col_axis),
                                  scatter=kw.get("scatter", True))
            else:
                out = allgather_gemm(a, b, mesh, row_axis, col_axis)
    if len(lead) != 1:
        out = out.reshape(*lead, out.shape[-1])
    return out
