"""Jit'd public wrappers for the Pallas kernels.

`tile_matmul` is the deployment entry point the model stack uses on TPU: it
pads to MXU-aligned block multiples (the placement-scheme alignment of §3.2.2
— irregular tiles are exactly what the paper's Insight 3 warns about), picks a
block shape that fits VMEM, and dispatches to the `mmad` kernel. On CPU (this
container) it routes through the pure-jnp oracle unless `interpret=True`
Pallas execution is requested explicitly — numerics are identical.

`local_matmul` is the schedule-resolved entry point: the mesh dataflows in
`core/gemm.py` call it with the lowered plan's `InnerKernel`, so the planner's
block geometry / pipeline depth / compute dtype choice actually reaches the
per-device GEMM. It is reverse-differentiable (`jax.custom_vjp`) so routed
training keeps working, and it never *narrows* operands: casting to the
kernel's dtype happens only when that dtype is at least as wide as the data —
quantizing to fp8/int8 is the model's decision, not the scheduler's.

Both take B as one matrix, or as a stack of layers (L, K, N) with the layer
to read (the serve step's layer loop): the kernel then reads that layer
where it lies, which needs blocks that divide its K and N and no cast
(`reads_layer_in_place`), since a pad or a cast of the stack would copy all
of it.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.ir import ELEM_BYTES_OF_DTYPE
from repro.core.schedule import INNER_VMEM_BUDGET, InnerKernel
from repro.kernels import ref
from repro.kernels.mmad import mmad

# VMEM working-set budget for picking block shapes (bytes); a v5e has ~128 MB
# but Pallas double-buffers every operand block, so stay well under. Shared
# with the schedule level: `InnerKernel.validate` and the lowering demotion
# enforce the same ceiling, so a plan-carried kernel always dispatches.
_VMEM_BUDGET = INNER_VMEM_BUDGET

# schedule dtype names -> jnp dtypes for the compute-dtype cast. fp8 uses the
# e4m3 variant jax ships (OCP float8_e4m3fn); accumulation is fp32 regardless.
_JNP_OF_DTYPE = {
    "float32": jnp.float32,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
    "float8_e4m3": jnp.float8_e4m3fn,
    "int8": jnp.int8,
}


def _round_up(x: int, mult: int) -> int:
    return (x + mult - 1) // mult * mult


def pick_block_shape(m: int, n: int, k: int, elem_bytes: int = 2
                     ) -> Tuple[int, int, int]:
    """MXU-aligned block shape whose double-buffered working set fits VMEM.

    This is the intra-chip analogue of the schedule abstraction's tiling
    choice: prefer (128, 128, bk) with the largest bk that fits (larger K
    chunks amortize the accumulator flush, the same effect as the paper's
    larger TK on the matrix engine).

    The returned `bk` always divides the 128-padded K (`_round_up(k, 128)`),
    so `tile_matmul`'s padding stays at the explicit 128-alignment — no
    silent reliance on bk-sized padding for ragged K."""
    bm = min(128, _round_up(m, 8))
    bn = min(128, _round_up(n, 128))
    kp = _round_up(k, 128)
    bk = 128
    while True:
        nxt = bk * 2
        ws = (bm * nxt + nxt * bn) * elem_bytes * 2 + bm * bn * 4
        if nxt <= kp and kp % nxt == 0 and ws <= _VMEM_BUDGET:
            bk = nxt
        else:
            break
    return bm, bn, min(bk, kp)


@functools.partial(jax.jit,
                   static_argnames=("block_shape", "interpret", "use_kernel",
                                    "out_dtype"))
def tile_matmul(a: jax.Array, b: jax.Array,
                block_shape: Optional[Tuple[int, int, int]] = None,
                interpret: bool = False,
                use_kernel: Optional[bool] = None,
                out_dtype=None,
                layer: Optional[jax.Array] = None) -> jax.Array:
    """C = A @ B via the Pallas MMAD kernel with padding to block multiples.

    With `layer`, `b` is a stack (L, K, N) and C = A @ b[layer], read in
    place: the blocks must divide K and N, since B is never padded then."""
    m, k = a.shape
    n = b.shape[-1]
    on_tpu = jax.default_backend() == "tpu"
    if use_kernel is None:
        use_kernel = on_tpu or interpret
    if not use_kernel:
        out = ref.mmad_ref(a, b if layer is None else b[layer])
        return out.astype(out_dtype) if out_dtype is not None else out

    bs = block_shape or pick_block_shape(m, n, k, a.dtype.itemsize)
    bm, bn, bk = bs
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    ap = jnp.pad(a, ((0, mp - m), (0, kp - k))) if (mp != m or kp != k) else a
    if layer is not None and (kp != k or np_ != n):
        raise ValueError(f"blocks {bs} do not divide a stacked B's layer "
                         f"{b.shape[1:]}: slice the layer out instead")
    bp = jnp.pad(b, ((0, kp - k), (0, np_ - n))) if (kp != k or np_ != n) else b
    out = mmad(ap, bp, block_shape=(bm, bn, bk), interpret=not on_tpu,
               out_dtype=out_dtype, layer=layer)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# Schedule-resolved local matmul (the two-level tuning dispatch point)
# ---------------------------------------------------------------------------

def _compute_dtype(dtype, kernel: InnerKernel):
    """The dtype an operand of `dtype` is computed in: the kernel's, UNLESS
    that would narrow the data.

    The planner may pick an fp8 kernel for an fp8-native part; if the model
    actually feeds fp32 activations, quantization is its call to make — the
    dispatch must not silently destroy precision. Widening (bf16 data on an
    fp32 kernel) is always safe."""
    want = _JNP_OF_DTYPE.get(kernel.dtype) if kernel.dtype else None
    if want is None:
        return dtype
    if ELEM_BYTES_OF_DTYPE[kernel.dtype] < jnp.dtype(dtype).itemsize:
        return dtype
    # never cross float/int kinds either (int8-kernel on fp8 data would
    # reinterpret values, not widen them)
    if (jnp.issubdtype(dtype, jnp.floating)
            != jnp.issubdtype(want, jnp.floating)):
        return dtype
    return want


def _cast_operand(x: jax.Array, kernel: InnerKernel) -> jax.Array:
    """`x` in the dtype the kernel computes it in (`_compute_dtype`)."""
    return x.astype(_compute_dtype(x.dtype, kernel))


def reads_layer_in_place(kernel: InnerKernel, k: int, n: int, dtype) -> bool:
    """Whether `local_matmul` under `kernel` can read a (K, N) layer of a
    stacked weight of `dtype` where it lies: the kernel's blocks divide K
    and N, and it computes in the weight's own dtype. A pad or a cast of
    the stack would copy all of it."""
    _, bn, bk = kernel.geometry()
    return (k % bk == 0 and n % bn == 0
            and jnp.dtype(_compute_dtype(dtype, kernel)) == jnp.dtype(dtype))


def local_matmul(a: jax.Array, b: jax.Array, kernel: InnerKernel,
                 interpret: bool = False,
                 layer: Optional[jax.Array] = None) -> jax.Array:
    """Per-device C = A @ B under a planner-resolved inner kernel, fp32 out.

    On TPU (or under `interpret=True`) this is the Pallas `mmad` kernel at
    the kernel's block geometry; on CPU it is the bitwise jnp oracle — the
    exact expression the mesh dataflows used before routing was kernel-aware,
    so enabling inner kernels does not move routed numerics on this host.
    Reverse-differentiable via `jax.custom_vjp` (transposed fp32 matmuls), so
    routed training works through the Pallas path too.

    With `layer`, `b` is a stack (L, K, N) and C = A @ b[layer], the layer
    read where it lies (`reads_layer_in_place` must hold). That form serves
    the decode layer loop and has no VJP: training slices its layers.
    """
    if layer is None:
        return _local_matmul(a, b, kernel, interpret)
    if not reads_layer_in_place(kernel, *b.shape[1:], b.dtype):
        raise ValueError(f"kernel {kernel.describe()} cannot read a "
                         f"{b.shape[1:]} {b.dtype} layer in place")
    return _local_matmul_impl(a, b, kernel, interpret, layer)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _local_matmul(a, b, kernel, interpret):
    return _local_matmul_impl(a, b, kernel, interpret)


def _local_matmul_impl(a, b, kernel, interpret, layer=None):
    a = _cast_operand(a, kernel)
    b = _cast_operand(b, kernel)
    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu or interpret):
        return jnp.dot(a, b if layer is None else b[layer],
                       preferred_element_type=jnp.float32)
    return tile_matmul(a, b, block_shape=kernel.geometry(),
                       interpret=interpret, use_kernel=True,
                       out_dtype=jnp.float32, layer=layer)


def _local_matmul_fwd(a, b, kernel, interpret):
    return _local_matmul_impl(a, b, kernel, interpret), (a, b)


def _local_matmul_bwd(kernel, interpret, res, g):
    a, b = res
    g32 = g.astype(jnp.float32)
    da = jnp.dot(g32, b.astype(jnp.float32).T,
                 preferred_element_type=jnp.float32).astype(a.dtype)
    db = jnp.dot(a.astype(jnp.float32).T, g32,
                 preferred_element_type=jnp.float32).astype(b.dtype)
    return da, db


_local_matmul.defvjp(_local_matmul_fwd, _local_matmul_bwd)
