"""Compile-only guards: the main path's kernels at olmo-1b's real widths,
compiled by the TPU compiler for a described (not attached) v5e chip.

Interpret mode cannot see what the chip's compiler refuses: a Pallas
block off the (8, 128) tiling, a kernel over the VMEM limit. These tests
compile the planner's `mmad` blocks for olmo-1b's decode and prefill
GEMMs, the `flat_attention` merge executor, and one routed decode step
(two layers at full width), so such a refusal fails here at no chip time.
Nothing runs: they assert only that the programs compile and, for the
routed step, that the Pallas kernel is in it (`tpu_custom_call`). One more
reads each benchmark cell's compiled serve step at the cell's sizes for
the buffers it holds: the KV cache carried through the layer loop and
written in place, with no copy of a layer or of the stack, and the layer
GEMM weights read by their kernels in their stacks, with no copy of a
layer or of a stack. The DeepSeek-V2 cell's step, at its whole cut, is
read the same way for its latent cache and its weights, expert stacks
among them, and for MoE layers that run their held experts as grouped
GEMMs with no one-hot dispatch tensor.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and the fixture skips the
module where it cannot be described. `local_matmul` chooses the kernel
from `jax.default_backend()`, which is the CPU here, so the tests steer
that choice to "tpu" around each compile.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.attention import flat_attention
from repro.core.lower import lower_attention, lower_schedule
from repro.core.schedule import AttnSchedule, AttnShape, GEMMShape
from repro.deploy.warmup import build_planner, warm_buckets
from repro.kernels.ops import local_matmul
from repro.launch import serve
from repro.launch.mesh import make_mesh
from repro.models import shard_ctx
from repro.models.model import decode_init, init_params
from repro.models.workload import gemm_workload
from repro.train.steps import make_serve_step
from serve_scopes import (_ARRAY, _NO_BUFFER, _buffers,
                          assert_names_every_part, compile_donated_steps,
                          kv_copies, weight_copies)

from bench import harness  # on the path serve_scopes sets
from bench.reduce import routed_gemms

BATCH, DECODE_MAX_LEN, PREFILL_LEN = 8, 2048, 256
OLMO = get_config("olmo-1b")
D, F, V = OLMO.d_model, OLMO.d_ff, OLMO.vocab
DECODE_GEMMS = [GEMMShape(BATCH, 3 * D, D), GEMMShape(BATCH, D, D),
                GEMMShape(BATCH, F, D), GEMMShape(BATCH, D, F),
                GEMMShape(BATCH, V, D)]
PREFILL_GEMM = GEMMShape(BATCH * PREFILL_LEN, F, D)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def chip_mesh(topo):
    return make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])


@pytest.fixture(scope="module")
def planner(tmp_path_factory):
    """Plans priced for the one chip the serve launcher holds."""
    p = build_planner(str(tmp_path_factory.mktemp("plans")), (1, 1),
                      max_candidates=12)
    warm_buckets(p, gemm_workload(OLMO, BATCH, DECODE_MAX_LEN, "decode")
                 + gemm_workload(OLMO, BATCH, PREFILL_LEN, "prefill"))
    return p


@contextlib.contextmanager
def compiling_for_tpu():
    """The TPU branch of `local_matmul`, and no persistent cache: entries
    written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture
def tpu_compile():
    with compiling_for_tpu():
        yield


def spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def compile_local_matmul(shape, planner, chip_mesh, one_chip):
    ep = lower_schedule(planner.plan(shape).schedule, chip_mesh, shape=shape)
    assert ep.inner_kernel is not None, ep.describe()
    a = spec((shape.m, shape.k), jnp.bfloat16, one_chip)
    b = spec((shape.k, shape.n), jnp.bfloat16, one_chip)
    compiled = jax.jit(lambda x, y: local_matmul(x, y, ep.inner_kernel)
                       ).lower(a, b).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape", DECODE_GEMMS,
                         ids=lambda s: f"{s.m}x{s.n}x{s.k}")
def test_decode_mmad_compiles(shape, planner, chip_mesh, one_chip,
                              tpu_compile):
    """Every decode GEMM at the planner's block, the 8x50304x2048 LM head
    among them (its block once came out 8x3144x512, which the compiler
    refuses)."""
    compile_local_matmul(shape, planner, chip_mesh, one_chip)


def test_prefill_mmad_compiles(planner, chip_mesh, one_chip, tpu_compile):
    compile_local_matmul(PREFILL_GEMM, planner, chip_mesh, one_chip)


def test_flat_attention_merge_compiles(chip_mesh, one_chip, tpu_compile):
    """The fused-attention merge executor on one decode step over a full
    2048-token cache at olmo-1b's head geometry."""
    h, hd = OLMO.n_heads, OLMO.d_model // OLMO.n_heads
    shape = AttnShape(BATCH, 1, DECODE_MAX_LEN, h, OLMO.n_kv_heads, hd, hd,
                      causal=True)
    ep = lower_attention(AttnSchedule(shape, "merge"), chip_mesh)
    assert ep.mode == "flat_merge", ep.describe()
    q = spec((BATCH, 1, h, hd), jnp.bfloat16, one_chip)
    kv = spec((BATCH, DECODE_MAX_LEN, OLMO.n_kv_heads, hd), jnp.bfloat16,
              one_chip)
    pos = spec((1,), jnp.int32, one_chip)
    klen = spec((BATCH,), jnp.int32, one_chip)
    jax.jit(lambda q, k, v, p, n: flat_attention(
        q, k, v, chip_mesh, ep, q_positions=p, kv_len=n)
            ).lower(q, kv, kv, pos, klen).compile()


def test_routed_decode_step_compiles(planner, chip_mesh, one_chip,
                                     tpu_compile):
    """One routed olmo-1b decode step (full width, two layers) through
    `pmm` -> `dit_gemm` -> `local_matmul`: the Pallas kernel is in it, and
    the step names every part of its work as the CPU compile does."""
    cfg = dataclasses.replace(OLMO, n_layers=2)

    def on_chip(tree):
        return jax.tree.map(lambda s: spec(s.shape, s.dtype, one_chip), tree)

    params = on_chip(jax.eval_shape(lambda k: init_params(k, cfg),
                                    jax.random.PRNGKey(0)))
    caches = on_chip(jax.eval_shape(
        lambda p: decode_init(p, cfg, BATCH, DECODE_MAX_LEN), params))
    tok = spec((BATCH, 1), jnp.int32, one_chip)
    pos = spec((), jnp.int32, one_chip)
    ctx = shard_ctx.GemmContext(mesh=chip_mesh, planner=planner)
    with shard_ctx.gemm_context(ctx):
        compiled = jax.jit(make_serve_step(cfg)).lower(
            params, caches, tok, pos).compile()
    assert ctx.stats.routed and ctx.stats.silent_degrades == 0
    assert "tpu_custom_call" in compiled.as_text()
    assert_names_every_part(compiled.as_text())


@pytest.fixture(scope="module",
                params=["olmo-1b.azure-code", "phi4-mini-3.8b.azure-conv"])
def cell_steps(request, chip_mesh, one_chip, tmp_path_factory):
    """A benchmark cell's routed serve step, compiled as the benchmark
    compiles it (cache donated) for the cell's prefill chunk and decode
    step: two layers at full width and the full vocabulary. The tied LM
    head reads the embedding as stored, so a decode step's temporaries
    stay under one layer's K at phi4-mini's 200,064 tokens too."""
    cell = harness.load_cell(request.param)
    batch, chunk, max_len = (cell.traffic[k]
                             for k in ("batch", "chunk", "max_len"))
    cfg = dataclasses.replace(get_config(cell.config["arch"]), n_layers=2)
    planner = serve.warm_plan_cache(
        cfg, batch, chunk, max_len, str(tmp_path_factory.mktemp("plans")),
        (1, 1), max_candidates=12)
    ctx = shard_ctx.GemmContext(mesh=chip_mesh, planner=planner)
    with compiling_for_tpu(), shard_ctx.gemm_context(ctx):
        steps = compile_donated_steps(cfg, batch, chunk, max_len,
                                      sharding=one_chip)
    assert ctx.stats.routed and ctx.stats.silent_degrades == 0
    return cfg, (batch, cfg.n_kv_heads, max_len, cfg.hd), steps, ctx.stats


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_step_carries_the_kv_cache_in_place(cell_steps, kind):
    """Outside the in-place write under `kv_cache.write`, no buffer copies,
    slices out or relayouts a whole layer's K/V or the whole stack; the
    attention products read the layer where it lies. The donated cache
    comes back in its own buffers, the layers stay one scan (routed GEMMs
    (7, 1), as the benchmark logs them), and a decode step's temporaries
    are smaller than one layer's K."""
    cfg, layer, steps, _ = cell_steps
    compiled = steps[kind]
    text = compiled.as_text()
    assert kv_copies(text, layer, cfg.n_layers) == []
    assert routed_gemms(text) == (7, 1)
    assert "tpu_custom_call" in text
    layer_bytes = math.prod(layer) * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= 2 * cfg.n_layers * layer_bytes
    if kind == "decode":
        assert mem.temp_size_in_bytes < layer_bytes


# stacked matrices the layer loop slices for its layers: the router (160
# columns, under a kernel block) and MLA's w_uk / w_uv, which XLA's own
# einsums read; every other matrix is a layer GEMM's weight
SLICED = ("router", "w_uk", "w_uv")


def layer_weight_shapes(cfg):
    """The shapes of every layer GEMM weight and expert stack of `cfg`'s
    parameters: each stack, and one layer of it."""
    params = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    out = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        if leaf.ndim >= 3 and path[-1].key not in SLICED:
            out |= {leaf.shape, leaf.shape[1:]}
    return out


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_step_reads_layer_weights_in_place(cell_steps, kind):
    """A decode step's kernels (one row block each) read their layer of
    each stack where it lies: no buffer outside the parameters has a layer
    GEMM weight's or a weight stack's shape, in any memory space. So do
    phi4-mini's prefill kernels (2,048 rows, 4 row blocks); olmo-1b's
    (6,144 rows, 12 row blocks, each of which would read all of B) are
    handed their layer sliced out, which XLA may stage in VMEM, and no
    stack is copied whole there either. XLA may still move a whole
    two-layer stack to VMEM before the loop (asynchronous copies, no
    relayout)."""
    cfg, _, steps, stats = cell_steps
    sliced_prefill = cfg.name.startswith("olmo")
    assert (stats.in_place, stats.sliced) == (
        (7, 7) if sliced_prefill else (14, 0))
    shapes = layer_weight_shapes(cfg)
    if kind == "prefill" and sliced_prefill:
        shapes = {s for s in shapes if len(s) == 3}
    assert weight_copies(steps[kind].as_text(), shapes, moves=True) == []


def head_reads(hlo_text: str, k: int, n: int):
    """What a compiled step's LM head reads of its (K, N) weight: the B
    operand of each Pallas kernel under `lm_head`, and every buffer other
    than the parameter and its views that holds the weight in any layout
    (transposed, padded by up to a block in N, or with a unit axis)."""
    kernels = []
    for line in hlo_text.splitlines():
        if ('custom_call_target="tpu_custom_call"' in line
                and "/lm_head/" in line):
            seg = line.split("operand_layout_constraints=")[1]
            operands = [tuple(int(d) for d in a.split(",") if d)
                        for a in _ARRAY.findall(seg.split("}}")[0])]
            kernels.append(operands[-1])
    copies = [(opcode, dims, op_name)
              for _, opcode, dims, _, op_name in _buffers(hlo_text)
              if opcode not in _NO_BUFFER
              for d in dims
              if len([x for x in d if x != 1]) == 2 and k in d
              and any(n <= x < n + 1024 for x in d if x != k)]
    return kernels, copies


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_step_reads_the_tied_head_as_stored(cell_steps, kind):
    """A decode step's tied LM head (one row block) reads the embedding
    (V, D) as stored: one Pallas kernel under `lm_head`, whose B is the
    embedding's (1, V, D) view, and no buffer outside the parameter holds
    the embedding transposed (D, V), padded (D, V') or broadcast
    (1, D, V'). A prefill chunk's head, compute-bound at 4 and 12 row
    blocks, is handed the one transposed copy (`MAX_TRANSPOSED_READS`),
    unpadded: the plan's 128-column block divides both vocabularies. Each
    is counted once."""
    cfg, _, steps, stats = cell_steps
    assert (stats.read_transposed, stats.copied_transposed) == (1, 1)
    kernels, copies = head_reads(steps[kind].as_text(), cfg.d_model,
                                 cfg.vocab)
    if kind == "decode":
        assert kernels == [(1, cfg.vocab, cfg.d_model)]
        assert copies == []
    else:
        assert kernels == [(1, cfg.d_model, cfg.vocab)]
        assert [opcode for opcode, _, _ in copies] == ["copy"], copies


MLA_MOE_CELL = "deepseek-v2-236b-ep8.azure-conv-b128"


@pytest.fixture(scope="module")
def mla_moe_steps(chip_mesh, one_chip, tmp_path_factory):
    """The DeepSeek-V2 cell's routed serve step at its whole cut (the dense
    layer and 4 MoE layers of 20 held experts, full widths and vocabulary)
    for its prefill chunk and decode step, compiled as the benchmark
    compiles it."""
    cell = harness.load_cell(MLA_MOE_CELL)
    cfg = cell.family.program_config(cell.config)
    batch, chunk, max_len = (cell.traffic[k]
                             for k in ("batch", "chunk", "max_len"))
    planner = serve.warm_plan_cache(
        cfg, batch, chunk, max_len, str(tmp_path_factory.mktemp("plans")),
        (1, 1), max_candidates=12)
    ctx = shard_ctx.GemmContext(mesh=chip_mesh, planner=planner)
    with compiling_for_tpu(), shard_ctx.gemm_context(ctx):
        steps = compile_donated_steps(cfg, batch, chunk, max_len,
                                      sharding=one_chip)
    assert ctx.stats.routed and ctx.stats.silent_degrades == 0
    return cell, cfg, (batch, max_len), steps, ctx.stats


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_mla_moe_step_groups_experts_and_carries_the_latent_cache(
        mla_moe_steps, kind):
    """The MoE layers run the held experts as grouped GEMMs (the Pallas
    `megablox.gmm` kernel, three under `moe.experts`, no XLA `ragged-dot`)
    and build no (groups, tokens, experts, capacity) one-hot dispatch
    tensor; the latent cache and rotary key stacks are donated, carried
    through both layer loops and written in place, with no copy of a layer
    or a stack; the routed GEMM kernels are those the family expects."""
    cell, cfg, (batch, max_len), steps, _ = mla_moe_steps
    compiled = steps[kind]
    text = compiled.as_text()
    grouped = [line for line in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in line
               and "/moe.experts/" in line]
    assert "ragged-dot" not in text
    assert len(grouped) == 3, grouped
    assert "gtec" not in text
    assert not any(len(d) == 4 and d[2] in (cfg.n_experts,
                                             cfg.held_experts[1])
                   for _, _, dims, _, _ in _buffers(text) for d in dims)
    n_moe = cfg.n_layers - cfg.n_dense_layers
    cache_bytes = 0
    for width in (cfg.kv_lora_rank, cfg.rope_head_dim):
        layer = (batch, max_len, width)
        cache_bytes += math.prod(layer) * 2 * cfg.n_layers
        # the dense group's stack is one layer deep, so its in-place write
        # reads as a layer to the MoE group's check; XLA may move that
        # 21 MB rotary stack to another memory space and back
        # (copy-start/copy-done, no relayout)
        moved = ("copy-start", "copy-done")
        assert [c for c in kv_copies(text, layer, cfg.n_dense_layers)
                if c[0] not in moved] == []
        for opcode, _, op_name in kv_copies(text, layer, n_moe):
            assert opcode in moved or (
                opcode == "dynamic-update-slice"
                and "/kv_cache.write/" in op_name), (opcode, op_name)
    dims = cell.family.dims(cell.config)
    assert routed_gemms(text) == cell.family.routed_expected(dims, kind)
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_mla_moe_step_reads_layer_weights_in_place(mla_moe_steps, kind):
    """No buffer outside the parameters has the shape of a layer GEMM
    weight, an expert stack or a held layer's experts: the MLA, FFN and
    shared-expert kernels read their layer of the stack in place (16 a
    step kind: one row block at decode, 4 in a 2,048-row prefill chunk,
    none sliced) and the grouped expert GEMMs their layer's experts in the
    (layers x experts) view of the stack. Let through: XLA's asynchronous
    moves of a whole stack to VMEM, and one relayout per step, before the
    loops, of the 64-column `mla.k_rope` stacks, which the TPU stores
    column-major. A prefill chunk's 12,288 expert rows (2,048 tokens x 6)
    at width 5,120 have the dense layer's down-projection's shape, which
    the decode step's check covers."""
    cell, cfg, (batch, max_len), steps, stats = mla_moe_steps
    assert (stats.in_place, stats.sliced) == (32, 0)
    shapes = layer_weight_shapes(cfg)
    if kind == "prefill":
        rows = batch * cell.traffic["chunk"] * cfg.moe_top_k
        shapes -= {(rows, cfg.d_model), (1, rows, cfg.d_model)}
    left = weight_copies(steps[kind].as_text(), shapes, moves=True)
    assert all(opcode == "copy" and dims[0][-1] < 128
               and "/while/" not in op_name
               for opcode, dims, op_name in left), left


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_mla_moe_step_reads_the_untied_head_as_before(mla_moe_steps, kind):
    """DeepSeek-V2's untied head is a (D, V) matrix: its one kernel reads
    `lm_head` (5120, 102400) as it did, with no copy, and nothing counts a
    transposed weight."""
    _, cfg, _, steps, stats = mla_moe_steps
    assert (stats.read_transposed, stats.copied_transposed) == (0, 0)
    kernels, copies = head_reads(steps[kind].as_text(), cfg.d_model,
                                 cfg.vocab)
    assert kernels == [(1, cfg.d_model, cfg.vocab)]
    assert copies == []
