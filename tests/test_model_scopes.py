"""The routed serve step names every part of its work (docs/observability.md):
olmo-1b at smoke width, routed through the planner on the host mesh,
compiled on the CPU for a prefill chunk and a decode step."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.launch import serve
from repro.launch.mesh import host_mesh_grid, make_host_mesh
from repro.models import shard_ctx
from repro.models.model import decode_init, init_params
from repro.train.steps import make_serve_step
from serve_scopes import assert_names_every_part, instructions

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
    """The layer scan's slicing of the stacked weights and caches, and its
    stacking of the new caches, carry no top-level scope; the norms, RoPE,
    embedding gather and LM head carry theirs."""
    names = [n for _, n in instructions(routed_steps[kind])]
    scan = [n for n in names if n.endswith(("/while/body/dynamic_slice",
                                            "/while/body/"
                                            "dynamic_update_slice"))]
    assert scan
    for part in ("/embed/", "/final_norm/norm/", "/block/norm/",
                 "/block/rope/", "/lm_head/pmm.lm_head/"):
        assert any(part in n for n in names), part
