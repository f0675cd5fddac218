"""A serve step's GEMMs read their weights as they are stored.

The serve step hands each layer's GEMMs their weight stack and the layer
index (`models.matmul.StoredWeight`) instead of a slice, and a tied LM
head the embedding (vocab, D), marked as the transpose of what it
multiplies by. A routed GEMM whose Pallas kernel can read the weight so
(one device, blocks that divide the layer, or a K block that divides the
embedding's K, no cast) does, counted `in_place` or `read_transposed`;
any other is handed the matrix copied out, counted `sliced` or
`copied_transposed`. Traced on the CPU at smoke sizes;
`tests/test_tpu_compile.py` reads the compiled steps at the benchmark
cells' sizes for copies of the weights.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.schedule import GEMMShape, InnerKernel, Schedule, Tiling
from repro.launch import serve
from repro.launch.mesh import host_mesh_grid, make_host_mesh, make_mesh
from repro.models import shard_ctx
from repro.models.matmul import StoredWeight, pmm
from repro.models.model import decode_init, init_params
from repro.train.steps import make_serve_step

KERNEL = InnerKernel(8, 128, 256, dtype="float32")


class OneKernelPlanner:
    """Plans every shape as SUMMA on one device with `KERNEL`."""

    def plan_cached(self, shape: GEMMShape):
        return Schedule(shape, Tiling(1, 1, 1, tk=shape.k), "summa",
                        inner_kernel=KERNEL)


@pytest.mark.parametrize("m, k, in_place", [
    (8, 512, True),       # one row block: the kernel reads the layer once
    (32, 512, True),      # 4 row blocks: 4 reads, MAX_LAYER_READS
    (8, 384, False),      # 384 = 1.5 blocks of 256: a pad would copy
    (40, 512, False),     # 5 row blocks: each would re-read the layer
], ids=["one-row-block", "four-row-blocks", "block-does-not-divide",
        "five-row-blocks"])
@pytest.mark.parametrize("layer", [0, 2])
def test_routed_gemm_reads_its_layer_or_slices_it(m, k, in_place, layer):
    """A kernel whose blocks divide the layer and whose row blocks read it
    at most `MAX_LAYER_READS` times reads the layer in place; any other is
    handed a slice, and each is counted."""
    rng = np.random.default_rng(layer)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    stack = jnp.asarray(rng.standard_normal((3, k, 256)), jnp.float32)
    ctx = shard_ctx.GemmContext(mesh=make_mesh((1, 1), ("data", "model")),
                                planner=OneKernelPlanner())
    with shard_ctx.gemm_context(ctx):
        out = jax.jit(lambda x, s, l: pmm(x, StoredWeight(s, l), tag="t"))(
            x, stack, jnp.int32(layer))
    assert (ctx.stats.in_place, ctx.stats.sliced) == (int(in_place),
                                                      int(not in_place))
    np.testing.assert_allclose(out, x @ stack[layer], rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("m, k, dtype, as_stored", [
    (8, 512, jnp.float32, True),     # one row block
    (16, 512, jnp.float32, False),   # two: a compute-bound GEMM
    (8, 384, jnp.float32, False),    # 384 = 1.5 K blocks: a pad would copy
    (8, 512, jnp.bfloat16, False),   # the float32 kernel would cast it
], ids=["one-row-block", "two-row-blocks", "k-block-does-not-divide",
        "cast"])
def test_routed_gemm_reads_a_transposed_weight_or_copies_it(m, k, dtype,
                                                            as_stored):
    """A weight stored as its transpose (a tied head's (V, K) embedding,
    V = 1000 not a multiple of the 256-column block) is read as stored
    where the kernel has one row block (`MAX_TRANSPOSED_READS`), its K
    block divides K and no cast is needed; any other GEMM is handed the
    transposed copy. Each is counted, and neither touches the layer
    counts."""
    rng = np.random.default_rng(m + k)
    x = jnp.asarray(rng.standard_normal((m, k)), dtype)
    embed = jnp.asarray(rng.standard_normal((1000, k)), dtype)
    ctx = shard_ctx.GemmContext(mesh=make_mesh((1, 1), ("data", "model")),
                                planner=OneKernelPlanner())
    with shard_ctx.gemm_context(ctx):
        out = jax.jit(lambda x, e: pmm(x, StoredWeight(e, transposed=True),
                                       tag="lm_head"))(x, embed)
    assert (ctx.stats.read_transposed, ctx.stats.copied_transposed) == (
        int(as_stored), int(not as_stored))
    assert (ctx.stats.in_place, ctx.stats.sliced) == (0, 0)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(x @ embed.T, np.float32),
                               rtol=1e-2, atol=1e-2)


MESH_BODY = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.schedule import InnerKernel, Schedule, Tiling
    from repro.launch.mesh import make_mesh
    from repro.models import shard_ctx
    from repro.models.matmul import StoredWeight, pmm

    class Planner:
        def plan_cached(self, shape):
            return Schedule(shape, Tiling(2, 2, 1, tk=shape.k), "summa",
                            inner_kernel=InnerKernel(8, 128, 256,
                                                     dtype="float32"))

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((16, 512)), jnp.float32)
    embed = jnp.asarray(rng.standard_normal((1024, 512)), jnp.float32)
    ctx = shard_ctx.GemmContext(mesh=make_mesh((2, 2), ("data", "model")),
                                planner=Planner())
    with shard_ctx.gemm_context(ctx):
        out = jax.jit(lambda x, e: pmm(
            x, StoredWeight(e, transposed=True), tag="lm_head"))(x, embed)
    assert (ctx.stats.read_transposed, ctx.stats.copied_transposed) == (0, 1)
    np.testing.assert_allclose(out, x @ embed.T, rtol=1e-4, atol=1e-4)
    print("ALL_OK")
""")


def test_transposed_weight_on_a_mesh_is_copied():
    """On a mesh of four devices the modes reshard B, so the GEMM is handed
    the transposed copy (fake devices, in a child process)."""
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", MESH_BODY], env=env,
                          capture_output=True, text=True, timeout=300)
    assert "ALL_OK" in proc.stdout, proc.stdout + proc.stderr


# widths on the (8, 128) kernel tiling, where the planner's blocks divide
# every layer GEMM (the smoke configurations' 160-wide FFN does not)
ALIGNED = {
    "olmo-1b": dict(d_model=256, n_heads=2, n_kv_heads=2, d_ff=512,
                    vocab=1024),
    "phi4-mini-3.8b": dict(d_model=256, n_heads=4, n_kv_heads=2, d_ff=512,
                           vocab=1024),
    "deepseek-v2-236b": dict(d_model=256, n_heads=2, kv_lora_rank=128,
                             q_lora_rank=128, nope_head_dim=64,
                             rope_head_dim=64, d_ff=256, moe_d_ff=128,
                             vocab=1024),
}


@pytest.mark.parametrize("arch, cut, expected", [
    # 7 projections a layer, in the one layer loop, and the tied head
    ("olmo-1b", ALIGNED["olmo-1b"], (7, 0, 1, 0)),
    ("phi4-mini-3.8b", ALIGNED["phi4-mini-3.8b"], (7, 0, 1, 0)),
    # the dense loop's 5 MLA projections and 3 FFN GEMMs, and the MoE
    # loop's 5 and 3 shared experts' (the router is sliced by the scan);
    # the untied head is a plain (D, V) matrix
    ("deepseek-v2-236b", ALIGNED["deepseek-v2-236b"], (16, 0, 0, 0)),
    # the 160-wide FFN's kernel is demoted to XLA's dot, which is handed
    # a slice: the attention projections read in place, the FFN sliced;
    # the head's kernel (K block 64) reads the 64-wide embedding as stored
    ("olmo-1b", {}, (4, 3, 1, 0)),
], ids=["olmo-1b", "phi4-mini-3.8b", "deepseek-v2-236b", "olmo-1b-160ffn"])
def test_serve_step_counts_layer_weights(arch, cut, expected, tmp_path):
    """Each routed layer GEMM of a prefill chunk's and a decode step's
    trace is counted once, read in place or sliced, and a tied head once,
    read as stored or copied (per step kind)."""
    cfg = dataclasses.replace(get_config(f"{arch}-smoke"), **cut)
    planner = serve.warm_plan_cache(cfg, 2, 16, 48, str(tmp_path),
                                    host_mesh_grid(), max_candidates=12)
    ctx = shard_ctx.GemmContext(mesh=make_host_mesh(), planner=planner)
    params = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    caches = jax.eval_shape(lambda: decode_init(None, cfg, 2, 48))
    with shard_ctx.gemm_context(ctx):
        for n in (16, 1):
            jax.jit(make_serve_step(cfg)).trace(
                params, caches, jax.ShapeDtypeStruct((2, n), jnp.int32),
                jax.ShapeDtypeStruct((n,), jnp.int32))
    counts = ("in_place", "sliced", "read_transposed", "copied_transposed")
    assert tuple(getattr(ctx.stats, c) for c in counts) == tuple(
        2 * c for c in expected)
    stats = ctx.stats.to_dict()
    assert tuple(stats[c] for c in counts) == tuple(2 * c for c in expected)
    assert shard_ctx.GemmStats.from_dict(stats).to_dict() == stats


def test_routed_step_matches_the_unrouted_one(tmp_path):
    """Reading the layers in place and the tied head as stored moves no
    logit: the routed olmo step (kernels on the stacks and the embedding)
    against plain `x @ w` on the same weights."""
    cfg = dataclasses.replace(get_config("olmo-1b-smoke"),
                              **ALIGNED["olmo-1b"], dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(0), cfg)
    caches = decode_init(params, cfg, 2, 48)
    tokens = jnp.arange(32, dtype=jnp.int32).reshape(2, 16)
    positions = jnp.arange(16, dtype=jnp.int32)
    want, _ = jax.jit(make_serve_step(cfg))(params, caches, tokens,
                                            positions)
    planner = serve.warm_plan_cache(cfg, 2, 16, 48, str(tmp_path),
                                    host_mesh_grid(), max_candidates=12)
    ctx = shard_ctx.GemmContext(mesh=make_host_mesh(), planner=planner)
    with shard_ctx.gemm_context(ctx):
        got, _ = jax.jit(make_serve_step(cfg))(params, caches, tokens,
                                               positions)
    assert ctx.stats.in_place == 7 and ctx.stats.sliced == 0
    assert ctx.stats.read_transposed == 1
    assert ctx.stats.copied_transposed == 0
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
