"""Per-kernel validation: shape/dtype sweeps of the Pallas kernels in
interpret mode against the pure-jnp oracles, plus the `local_matmul`
parity contract the mesh dataflows rely on: on CPU the schedule-resolved
local GEMM is BITWISE the `jnp.dot` fp32 oracle (routing through the
kernel funnel must not move routed numerics on this host), casts never
narrow the data, and gradients flow through the custom_vjp.

The property-based tests need hypothesis (requirements-dev.txt); the
parity and contract tests run without it so the local fast lane still
covers the dispatch path.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.schedule import InnerKernel
from repro.kernels import ref
from repro.kernels.mmad import mmad
from repro.kernels.ops import (_VMEM_BUDGET, local_matmul, pick_block_shape,
                               tile_matmul)

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # CI installs requirements-dev; local lane may not
    HAVE_HYPOTHESIS = False

RNG = np.random.default_rng(42)


def _mk(m, k, n, dtype):
    a = jnp.asarray(RNG.standard_normal((m, k)), dtype=dtype)
    b = jnp.asarray(RNG.standard_normal((k, n)), dtype=dtype)
    return a, b


TOL = {jnp.float32: dict(rtol=1e-4, atol=1e-4),
       jnp.bfloat16: dict(rtol=5e-2, atol=5e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", [
    (128, 128, 128), (256, 128, 128), (128, 384, 256), (256, 256, 512),
])
def test_mmad_shape_sweep(shape, dtype):
    m, k, n = shape
    a, b = _mk(m, k, n, dtype)
    out = mmad(a, b, block_shape=(128, 128, 128), interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref.mmad_ref(a, b), np.float32),
                               **TOL[dtype])


@pytest.mark.parametrize("bs", [(128, 128, 128), (64, 128, 128), (128, 256, 64)])
def test_mmad_block_shapes(bs):
    m = 2 * bs[0]
    n = 2 * bs[1]
    k = 2 * bs[2]
    a, b = _mk(m, k, n, jnp.float32)
    out = mmad(a, b, block_shape=bs, interpret=True)
    np.testing.assert_allclose(out, ref.mmad_ref(a, b), rtol=1e-4, atol=1e-4)


def test_mmad_out_dtype():
    a, b = _mk(128, 128, 128, jnp.bfloat16)
    out = mmad(a, b, interpret=True, out_dtype=jnp.float32)
    assert out.dtype == jnp.float32


def test_mmad_rejects_ragged():
    a, b = _mk(100, 128, 128, jnp.float32)
    with pytest.raises(ValueError, match="not divisible"):
        mmad(a, b, block_shape=(128, 128, 128), interpret=True)


@pytest.mark.parametrize("block", [(8, 128, 128), (16, 256, 128)],
                         ids=lambda b: "x".join(map(str, b)))
@pytest.mark.parametrize("layer", [0, 2])
def test_mmad_reads_a_layer_of_a_stack(layer, block):
    """B given as a stack (L, K, N) and a layer index reads that layer where
    it lies: the same numbers as `mmad` on the layer sliced out."""
    a = jnp.asarray(RNG.standard_normal((16, 256)), jnp.float32)
    stack = jnp.asarray(RNG.standard_normal((3, 256, 512)), jnp.float32)
    out = mmad(a, stack, block_shape=block, interpret=True,
               layer=jnp.int32(layer))
    np.testing.assert_array_equal(
        out, mmad(a, stack[layer], block_shape=block, interpret=True))


def test_stacked_weight_is_never_padded():
    """A block that does not divide the layer is refused, not padded: a pad
    of the stack would copy all of it, so the caller slices instead."""
    a = jnp.ones((16, 384), jnp.float32)
    stack = jnp.ones((2, 384, 256), jnp.float32)
    with pytest.raises(ValueError, match="do not divide"):
        tile_matmul(a, stack, block_shape=(8, 128, 256), interpret=True,
                    use_kernel=True, layer=jnp.int32(1))
    with pytest.raises(ValueError, match="cannot read"):
        local_matmul(a, stack, InnerKernel(8, 128, 256, dtype="float32"),
                     layer=jnp.int32(1))


# B stored as its transpose (N, K), as a tied LM head's embedding (V, D) is:
# blocks of 256 columns divide V = 1024 and leave V = 1000 a ragged last
# block; M of 8 and 16 fill one or two row blocks of 8, 40 fills five
NT_BLOCK = (8, 256, 128)


def _nt_product(entry, a, e):
    if entry == "mmad":
        return mmad(a, e, block_shape=NT_BLOCK, interpret=True,
                    out_dtype=jnp.float32, transposed=True)
    if entry == "tile_matmul":
        return tile_matmul(a, e, block_shape=NT_BLOCK, interpret=True,
                           use_kernel=True, out_dtype=jnp.float32,
                           transposed=True)
    return local_matmul(a, e, InnerKernel(*NT_BLOCK, dtype="float32"),
                        interpret=True, transposed=True)


@pytest.mark.parametrize("entry", ["mmad", "tile_matmul", "local_matmul"])
@pytest.mark.parametrize("v", [1024, 1000], ids=["v-divides", "v-ragged"])
@pytest.mark.parametrize("m", [8, 16, 40])
def test_kernel_reads_b_stored_transposed(entry, v, m):
    """B handed over as stored, its transpose E (V, K): every entry point
    gives A @ E.T, the ragged last block of V included, and the output is
    (M, V)."""
    a = jnp.asarray(RNG.standard_normal((m, 256)), jnp.float32)
    e = jnp.asarray(RNG.standard_normal((v, 256)), jnp.float32)
    out = _nt_product(entry, a, e)
    assert out.shape == (m, v)
    np.testing.assert_allclose(out, _oracle(a, e.T), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("layer", [0, 2])
def test_mmad_reads_a_transposed_layer_of_a_stack(layer):
    """A stack of transposed matrices (L, N, K) and a layer index: the
    same numbers as the layer sliced out."""
    a = jnp.asarray(RNG.standard_normal((16, 256)), jnp.float32)
    stack = jnp.asarray(RNG.standard_normal((3, 1000, 256)), jnp.float32)
    out = mmad(a, stack, block_shape=NT_BLOCK, interpret=True,
               layer=jnp.int32(layer), transposed=True)
    np.testing.assert_array_equal(
        out, mmad(a, stack[layer], block_shape=NT_BLOCK, interpret=True,
                  transposed=True))


def test_transposed_weight_is_never_padded():
    """A stored-transposed B is read as it lies: the traced product pads
    and transposes nothing (a ragged V included), and a K block that does
    not divide K is refused, not padded."""
    a = jnp.ones((16, 256), jnp.float32)
    e = jnp.ones((1000, 256), jnp.float32)
    jaxpr = str(jax.make_jaxpr(lambda a, e: tile_matmul(
        a, e, block_shape=NT_BLOCK, interpret=True, use_kernel=True,
        transposed=True))(a, e))
    assert "pallas_call" in jaxpr
    assert not re.findall(r"\b(pad|transpose|slice)\[", jaxpr), jaxpr
    e384 = jnp.ones((1000, 384), jnp.float32)
    with pytest.raises(ValueError, match="do not divide"):
        tile_matmul(jnp.ones((16, 384), jnp.float32), e384,
                    block_shape=(8, 256, 256), interpret=True,
                    use_kernel=True, transposed=True)
    with pytest.raises(ValueError, match="cannot read"):
        local_matmul(jnp.ones((16, 384), jnp.float32), e384,
                     InnerKernel(8, 256, 256, dtype="float32"),
                     transposed=True)


# ---------------------------------------------------------------------------
# local_matmul: the schedule-resolved per-device GEMM
# ---------------------------------------------------------------------------

def _oracle(a, b):
    """The exact expression the mesh dataflows used before routing was
    kernel-aware — the bitwise bar for the CPU path."""
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


KER32 = InnerKernel(128, 128, 128, dtype="float32")


def test_local_matmul_cpu_bitwise_oracle():
    """On CPU (non-interpret) the kernel path IS the oracle, bit for bit —
    enabling inner kernels cannot move routed numerics on this host."""
    a, b = _mk(192, 256, 160, jnp.float32)
    out = local_matmul(a, b, KER32)
    assert out.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(out), np.asarray(_oracle(a, b)))


@pytest.mark.parametrize("dtype_name,jdt,tol", [
    ("float32", jnp.float32, 1e-4),
    ("bfloat16", jnp.bfloat16, 1e-2),
    ("float8_e4m3", jnp.float8_e4m3fn, 1e-2),
], ids=["f32", "bf16", "fp8"])
def test_local_matmul_interpret_matches_oracle(dtype_name, jdt, tol):
    """interpret=True runs the real Pallas mmad at the kernel's geometry;
    products of the (already-quantized) operands are exact in the fp32
    accumulator, so only accumulation order separates it from the oracle.
    Ragged shape exercises the padding path."""
    a32, b32 = _mk(160, 192, 224, jnp.float32)
    a, b = a32.astype(jdt), b32.astype(jdt)
    kernel = InnerKernel(128, 128, 128, dtype=dtype_name)
    out = local_matmul(a, b, kernel, True)
    want = _oracle(a.astype(jnp.float32), b.astype(jnp.float32))
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=tol, atol=tol)


def test_local_matmul_never_downcasts():
    """An fp8 kernel on fp32 data must NOT quantize — precision is the
    model's call, not the scheduler's. Output stays bitwise the oracle."""
    a, b = _mk(128, 256, 128, jnp.float32)
    kernel = InnerKernel(128, 128, 128, dtype="float8_e4m3")
    np.testing.assert_array_equal(np.asarray(local_matmul(a, b, kernel)),
                                  np.asarray(_oracle(a, b)))


def test_local_matmul_no_float_int_crossing():
    """An int8 kernel on fp8 data would reinterpret values (equal byte
    width, different kind) — the cast must refuse."""
    a32, b32 = _mk(128, 128, 128, jnp.float32)
    a, b = a32.astype(jnp.float8_e4m3fn), b32.astype(jnp.float8_e4m3fn)
    kernel = InnerKernel(128, 128, 128, dtype="int8")
    np.testing.assert_array_equal(np.asarray(local_matmul(a, b, kernel)),
                                  np.asarray(_oracle(a, b)))


def test_local_matmul_widening_cast():
    """bf16 data on an fp32 kernel widens (always safe) before the dot."""
    a32, b32 = _mk(64, 128, 64, jnp.float32)
    a, b = a32.astype(jnp.bfloat16), b32.astype(jnp.bfloat16)
    out = local_matmul(a, b, KER32)
    want = _oracle(a.astype(jnp.float32), b.astype(jnp.float32))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_local_matmul_grad_parity():
    """The custom_vjp's transposed fp32 matmuls agree with autodiff of the
    oracle — routed training through the kernel path stays correct."""
    a, b = _mk(96, 128, 80, jnp.float32)

    def loss_kernel(x, y):
        return (local_matmul(x, y, KER32) ** 2).sum()

    def loss_oracle(x, y):
        return (_oracle(x, y) ** 2).sum()

    ga_k, gb_k = jax.grad(loss_kernel, argnums=(0, 1))(a, b)
    ga_o, gb_o = jax.grad(loss_oracle, argnums=(0, 1))(a, b)
    for got, want in ((ga_k, ga_o), (gb_k, gb_o)):
        want = np.asarray(want)
        # the custom VJP and autodiff sum the same fp32 products in a
        # different order: reassociation error is a few fp32 ulps of the
        # gradient's largest entry (|g| ~ 1e3 here, off by ~3e-7 of it),
        # so the absolute tolerance scales with that magnitude
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-6 * np.abs(want).max())
    assert ga_k.dtype == a.dtype and gb_k.dtype == b.dtype


def test_inner_kernel_roundtrip_and_budget():
    ik = InnerKernel(128, 128, 512, depth=1, dtype="bfloat16")
    assert InnerKernel.from_dict(ik.to_dict()) == ik
    assert ik.describe() == "128x128x512d1:bfloat16"
    assert ik.working_set_bytes() <= _VMEM_BUDGET
    big = InnerKernel(2048, 2048, 2048, dtype="float32")
    assert big.working_set_bytes() > _VMEM_BUDGET


# ---------------------------------------------------------------------------
# pick_block_shape: the VMEM-budget / divisibility contract
# ---------------------------------------------------------------------------

def _check_block_contract(m, n, k, eb):
    bm, bn, bk = pick_block_shape(m, n, k, eb)
    kp = -(-k // 128) * 128
    assert bm % 8 == 0 and bn % 128 == 0, (bm, bn)
    # bk always divides the 128-padded K — tile_matmul's padding stays at
    # the explicit 128 alignment, never silently bk-sized
    assert 1 <= bk <= kp and kp % bk == 0, (bk, kp)
    ws = (bm * bk + bk * bn) * eb * 2 + bm * bn * 4
    assert ws <= _VMEM_BUDGET, (bm, bn, bk, ws)


@pytest.mark.parametrize("m,n,k,eb", [
    (1, 1, 1, 4), (8, 128, 127, 2), (100, 300, 129, 1),
    (4096, 4096, 4096, 4), (128, 128, 1 << 20, 2), (7, 9, 999, 4),
    (128, 128, 384, 1),  # kp not a power of two: bk must still divide it
])
def test_pick_block_shape_contract(m, n, k, eb):
    _check_block_contract(m, n, k, eb)


def test_pick_block_shape_alignment():
    bm, bn, bk = pick_block_shape(4096, 4096, 4096, elem_bytes=2)
    assert bm % 8 == 0 and bn % 128 == 0 and bk % 128 == 0
    # double-buffered working set fits the budget
    assert (bm * bk + bk * bn) * 2 * 2 + bm * bn * 4 <= 8 * 1024 * 1024


def test_splitk_ref_matches_dense():
    a, b = _mk(64, 256, 64, jnp.float32)
    np.testing.assert_allclose(ref.splitk_ref(a, b, splits=4),
                               ref.mmad_ref(a, b), rtol=1e-4, atol=1e-4)


def test_flash_attention_ref_causal():
    q = jnp.asarray(RNG.standard_normal((2, 16, 8)), dtype=jnp.float32)
    k = jnp.asarray(RNG.standard_normal((2, 16, 8)), dtype=jnp.float32)
    v = jnp.asarray(RNG.standard_normal((2, 16, 8)), dtype=jnp.float32)
    out = ref.flash_attention_ref(q, k, v, causal=True)
    assert out.shape == q.shape
    # first query position attends only to itself
    np.testing.assert_allclose(out[:, 0], v[:, 0], rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# property tests (hypothesis, requirements-dev.txt)
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @given(m=st.integers(1, 300), k=st.integers(1, 300), n=st.integers(1, 300))
    @settings(max_examples=12, deadline=None)
    def test_tile_matmul_padding_property(m, k, n):
        """tile_matmul must agree with the oracle for ANY shape (pads
        internally)."""
        a = jnp.asarray(RNG.standard_normal((m, k)), dtype=jnp.float32)
        b = jnp.asarray(RNG.standard_normal((k, n)), dtype=jnp.float32)
        out = tile_matmul(a, b, interpret=True, use_kernel=True)
        np.testing.assert_allclose(out, a @ b, rtol=1e-3, atol=1e-3)

    @given(m=st.integers(1, 8192), n=st.integers(1, 8192),
           k=st.integers(1, 1 << 16), eb=st.sampled_from([1, 2, 4]))
    @settings(max_examples=60, deadline=None)
    def test_pick_block_shape_property(m, n, k, eb):
        """For ANY problem shape and element width: MXU alignment, bk
        divides the 128-padded K, and the double-buffered working set
        stays under the VMEM budget."""
        _check_block_contract(m, n, k, eb)

else:  # keep the skip visible in local runs without hypothesis

    @pytest.mark.skip(reason="property tests need hypothesis "
                      "(requirements-dev.txt)")
    def test_property_suite_needs_hypothesis():
        pass
