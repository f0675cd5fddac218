"""Pallas TPU kernel for the per-tile MMAD (paper Fig. 3b, adapted to TPU).

On SoftHier a compute tile's matrix engine consumes L1-resident A/B tiles and
accumulates C in L1. The TPU analogue: a Pallas kernel whose BlockSpec tiling
streams (bm x bk) / (bk x bn) blocks HBM->VMEM (the placement-scheme tiles of
§3.2.2), feeds the MXU, and keeps a float32 VMEM accumulator across the K
grid dimension — Pallas's implicit pipelining of the grid is the paper's
§3.3.1 double-buffered DMA/compute overlap.

B may be a stack of matrices (L, K, N), a model's stacked layer weights,
with the layer to read as a scalar-prefetch operand: B's block index map
reads it, so the kernel streams its blocks of that layer where they lie
and no copy of the layer is made. A 2-D B is a stack of one.

B may also be stored as its transpose, (N, K) or (L, N, K), as a tied LM
head's embedding (vocab, D) is: its blocks are then (bn, bk) and each step
contracts both operands' last axes, so no transposed copy is made. That
form pads nothing: its grid over N is `pl.cdiv(N, bn)`, the last block's
rows past N are never written back.

Block shapes default to MXU-aligned (128, 128, 128); the K loop is the
innermost ("arbitrary") grid dimension so the accumulator scratch carries
across it, while M/N are "parallel" dimensions.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mmad_kernel(layer_ref, a_ref, b_ref, o_ref, acc_ref, *, n_k: int,
                 out_dtype, transposed: bool = False):
    """One (i, j, k) grid step: acc += A[i,k] @ B[k,j] (B[j,k]^T when B is
    stored transposed); flush at k == n_k-1. `layer_ref` is read by B's
    index map only."""
    del layer_ref
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if transposed:
        acc_ref[...] += jax.lax.dot_general(
            a_ref[...], b_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(out_dtype)


@functools.partial(jax.jit, static_argnames=("block_shape", "interpret",
                                             "out_dtype", "transposed"))
def mmad(a: jax.Array, b: jax.Array,
         block_shape: Tuple[int, int, int] = (128, 128, 128),
         interpret: bool = False,
         out_dtype: Optional[jnp.dtype] = None,
         layer: Optional[jax.Array] = None,
         transposed: bool = False) -> jax.Array:
    """C = A @ B with VMEM-tiled blocks and a float32 accumulator.

    `b` is (K, N), or a stack (L, K, N) with `layer` the (traced) index of
    the matrix to multiply by, read in place; `transposed`: B is stored
    as its transpose, (N, K) or (L, N, K). Shapes must divide by the block
    shape (the ops.py wrapper pads otherwise), except N when B is stored
    transposed.
    """
    if (b.ndim == 3) != (layer is not None):
        raise ValueError(f"a stacked B needs a layer index and a 2-D B none: "
                         f"B {b.shape}, layer {layer}")
    if layer is None:
        b, layer = b[None], 0
    m, k = a.shape
    if transposed:
        _, n, k2 = b.shape
    else:
        _, k2, n = b.shape
    if k != k2:
        raise ValueError(f"inner dims mismatch: {a.shape} @ {b.shape}")
    bm, bn, bk = block_shape
    if m % bm or (n % bn and not transposed) or k % bk:
        raise ValueError(f"shape ({m},{n},{k}) not divisible by blocks {block_shape}")
    out_dtype = out_dtype or a.dtype
    n_k = k // bk

    kernel = functools.partial(_mmad_kernel, n_k=n_k, out_dtype=out_dtype,
                               transposed=transposed)
    if transposed:
        b_spec = pl.BlockSpec((None, bn, bk), lambda i, j, kk, l: (l[0], j, kk))
    else:
        b_spec = pl.BlockSpec((None, bk, bn), lambda i, j, kk, l: (l[0], kk, j))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m // bm, pl.cdiv(n, bn), n_k),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda i, j, kk, l: (i, kk)),
                b_spec,
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk, l: (i, j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), a, b)
