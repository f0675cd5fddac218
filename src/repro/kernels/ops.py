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
(`reads_in_place`), since a pad or a cast of the stack would copy all of
it. B may also be stored as its transpose, (N, K), as a tied LM head's
embedding is (`transposed`): the kernel then reads it as stored, which
needs a K block that divides K and no cast; N may be ragged, since that
form's grid runs past N and pads nothing.
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


def _stored(b: jax.Array, layer: Optional[jax.Array],
            transposed: bool) -> jax.Array:
    """The (K, N) matrix a stored B stands for, copied out (the oracles'
    operand): b[layer] of a stack, transposed back if stored so."""
    b = b if layer is None else b[layer]
    return b.T if transposed else b


@functools.partial(jax.jit,
                   static_argnames=("block_shape", "interpret", "use_kernel",
                                    "out_dtype", "transposed"))
def tile_matmul(a: jax.Array, b: jax.Array,
                block_shape: Optional[Tuple[int, int, int]] = None,
                interpret: bool = False,
                use_kernel: Optional[bool] = None,
                out_dtype=None,
                layer: Optional[jax.Array] = None,
                transposed: bool = False) -> jax.Array:
    """C = A @ B via the Pallas MMAD kernel with padding to block multiples.

    With `layer`, `b` is a stack (L, K, N) and C = A @ b[layer], read in
    place: the blocks must divide K and N, since B is never padded then.
    With `transposed`, `b` is stored as B's transpose, (N, K) (or a stack
    (L, N, K)), and read as stored: the K block must divide K, and N needs
    no pad (`mmad`'s grid runs past it)."""
    m, k = a.shape
    n = b.shape[-2] if transposed else b.shape[-1]
    on_tpu = jax.default_backend() == "tpu"
    if use_kernel is None:
        use_kernel = on_tpu or interpret
    if not use_kernel:
        out = ref.mmad_ref(a, _stored(b, layer, transposed))
        return out.astype(out_dtype) if out_dtype is not None else out

    bs = block_shape or pick_block_shape(m, n, k, a.dtype.itemsize)
    bm, bn, bk = bs
    mp, kp = _round_up(m, bm), _round_up(k, bk)
    np_ = n if transposed else _round_up(n, bn)
    ap = jnp.pad(a, ((0, mp - m), (0, kp - k))) if (mp != m or kp != k) else a
    if (layer is not None or transposed) and (kp != k or np_ != n):
        raise ValueError(f"blocks {bs} do not divide B {b.shape} as stored: "
                         f"hand over the matrix copied out instead")
    bp = jnp.pad(b, ((0, kp - k), (0, np_ - n))) if (kp != k or np_ != n) else b
    out = mmad(ap, bp, block_shape=(bm, bn, bk), interpret=not on_tpu,
               out_dtype=out_dtype, layer=layer, transposed=transposed)
    return out[:m] if transposed else out[:m, :n]


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


def reads_in_place(kernel: InnerKernel, k: int, n: int, dtype,
                   transposed: bool = False) -> bool:
    """Whether `local_matmul` under `kernel` can read a (K, N) weight of
    `dtype` as it is stored, a layer of a stack or (`transposed`) an
    (N, K) matrix: the kernel's blocks divide K, and N unless the weight
    is stored transposed, and it computes in the weight's own dtype. A
    pad or a cast of the stored array would copy all of it."""
    _, bn, bk = kernel.geometry()
    return (k % bk == 0 and (transposed or n % bn == 0)
            and jnp.dtype(_compute_dtype(dtype, kernel)) == jnp.dtype(dtype))


def local_matmul(a: jax.Array, b: jax.Array, kernel: InnerKernel,
                 interpret: bool = False,
                 layer: Optional[jax.Array] = None,
                 transposed: bool = False) -> jax.Array:
    """Per-device C = A @ B under a planner-resolved inner kernel, fp32 out.

    On TPU (or under `interpret=True`) this is the Pallas `mmad` kernel at
    the kernel's block geometry; on CPU it is the bitwise jnp oracle — the
    exact expression the mesh dataflows used before routing was kernel-aware,
    so enabling inner kernels does not move routed numerics on this host.
    Reverse-differentiable via `jax.custom_vjp` (transposed fp32 matmuls), so
    routed training works through the Pallas path too.

    With `layer`, `b` is a stack (L, K, N) and C = A @ b[layer], the layer
    read where it lies; with `transposed`, `b` is stored as B's transpose,
    (N, K), and read as stored (`reads_in_place` must hold). These forms
    serve the serve step's layer loop and tied LM head and have no VJP:
    training hands over the matrix copied out.
    """
    if layer is None and not transposed:
        return _local_matmul(a, b, kernel, interpret)
    k, n = b.shape[-2:]
    if transposed:
        k, n = n, k
    if not reads_in_place(kernel, k, n, b.dtype, transposed):
        raise ValueError(f"kernel {kernel.describe()} cannot read a "
                         f"{b.shape} {b.dtype} B as stored")
    return _local_matmul_impl(a, b, kernel, interpret, layer, transposed)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _local_matmul(a, b, kernel, interpret):
    return _local_matmul_impl(a, b, kernel, interpret)


def _local_matmul_impl(a, b, kernel, interpret, layer=None,
                       transposed=False):
    a = _cast_operand(a, kernel)
    b = _cast_operand(b, kernel)
    on_tpu = jax.default_backend() == "tpu"
    if not (on_tpu or interpret):
        return jnp.dot(a, _stored(b, layer, transposed),
                       preferred_element_type=jnp.float32)
    return tile_matmul(a, b, block_shape=kernel.geometry(),
                       interpret=interpret, use_kernel=True,
                       out_dtype=jnp.float32, layer=layer,
                       transposed=transposed)


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
