"""The routed serve step names every part of its work (docs/observability.md)
and carries its KV cache through the layer loop in place: olmo-1b at smoke
width, routed through the planner on the host mesh, compiled on the CPU for
a prefill chunk and a decode step."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.launch import serve
from repro.launch.mesh import host_mesh_grid, make_host_mesh
from repro.models import shard_ctx
from repro.models.model import decode_init, init_params
from repro.train.steps import make_serve_step
from serve_scopes import (assert_names_every_part, compile_donated_steps,
                          instructions, kv_copies)

BATCH, CHUNK, MAX_LEN = 2, 16, 48


@pytest.fixture(scope="module")
def routed_steps(tmp_path_factory):
    cfg = get_config("olmo-1b-smoke")
    planner = serve.warm_plan_cache(
        cfg, BATCH, CHUNK, MAX_LEN, str(tmp_path_factory.mktemp("plans")),
        host_mesh_grid(), max_candidates=12)
    ctx = shard_ctx.GemmContext(mesh=make_host_mesh(), planner=planner)
    params = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    caches = jax.eval_shape(lambda: decode_init(None, cfg, BATCH, MAX_LEN))
    hlo = {}
    with shard_ctx.gemm_context(ctx):
        for kind, n in (("prefill", CHUNK), ("decode", 1)):
            hlo[kind] = jax.jit(make_serve_step(cfg)).lower(
                params, caches, jax.ShapeDtypeStruct((BATCH, n), jnp.int32),
                jax.ShapeDtypeStruct((n,), jnp.int32)).compile().as_text()
    assert ctx.stats.routed and ctx.stats.silent_degrades == 0
    return hlo


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_routed_serve_step_names_every_part(routed_steps, kind):
    assert_names_every_part(routed_steps[kind])


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_scan_plumbing_stays_outside_the_block(routed_steps, kind):
    """The layer scan slices no weight out of its stacks (olmo's layers
    have no leaf but GEMM weights, which their GEMMs read by layer: any
    slice of one sits under the `pmm.<tag>` that reads it), and the scan
    stacks nothing: the KV cache rides in its carry, so the only cache
    writes are the block's own under `kv_cache.write`. The norms, RoPE,
    embedding gather and LM head carry their scopes."""
    names = [n for _, n in instructions(routed_steps[kind])]
    assert not [n for n in names if n.endswith("/while/body/dynamic_slice")]
    assert not [n for n in names
                if n.endswith("/dynamic_update_slice")
                and "/kv_cache.write/" not in n]
    for part in ("/embed/", "/final_norm/norm/", "/block/norm/",
                 "/block/rope/", "/lm_head/pmm.lm_head/"):
        assert any(part in n for n in names), part


# The cache guard's own sizes: no activation of the step has a layer's
# K/V dims (B, Hkv, S, D) = (2, 4, 40, 16) in any order.
GUARD_CHUNK, GUARD_MAX_LEN = 8, 40


@pytest.fixture(scope="module")
def donated_f32_steps(tmp_path_factory):
    """The routed olmo smoke step as the benchmark compiles it, cache
    donated, in float32: the CPU runs a bfloat16 dynamic_update_slice
    through float32 copies of the whole operand, which the TPU does not."""
    cfg = dataclasses.replace(get_config("olmo-1b-smoke"), dtype=jnp.float32)
    planner = serve.warm_plan_cache(
        cfg, BATCH, GUARD_CHUNK, GUARD_MAX_LEN,
        str(tmp_path_factory.mktemp("plans")), host_mesh_grid(),
        max_candidates=12)
    ctx = shard_ctx.GemmContext(mesh=make_host_mesh(), planner=planner)
    with shard_ctx.gemm_context(ctx):
        steps = compile_donated_steps(cfg, BATCH, GUARD_CHUNK, GUARD_MAX_LEN)
    assert ctx.stats.routed and ctx.stats.silent_degrades == 0
    return cfg, steps


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_kv_cache_is_carried_and_written_in_place(donated_f32_steps, kind):
    """No buffer of the step copies, slices out or relayouts a whole
    layer's K/V or a whole stack, beyond the in-place write under
    `kv_cache.write` and the layer read the CPU's dot takes as its operand;
    the donated cache comes back in its own buffers."""
    cfg, steps = donated_f32_steps
    layer = (BATCH, cfg.n_kv_heads, GUARD_MAX_LEN, cfg.hd)
    compiled = steps[kind]
    assert kv_copies(compiled.as_text(), layer, cfg.n_layers,
                     dot_operands=True) == []
    stacks = 2 * cfg.n_layers * math.prod(layer) * 4
    assert compiled.memory_analysis().alias_size_in_bytes >= stacks
