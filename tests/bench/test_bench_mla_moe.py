"""The MLA + MoE family (DeepSeek-V2's block) of the benchmark, on the CPU.

- the family provides the whole contract, and its adapter gives the
  program's parameter layout at the smoke width;
- a prefill chunk and then decode steps through the cache of the serve
  step agree with the float32 reference (`bench/reference/mla_moe.py`);
- the reference's served-token gaps read its own best logit, and 0 where
  its routing lies within the tie of deciding the held experts' part; that
  margin bounds every change of the held experts under moved logits;
- the work counts equal numbers derived by hand from the published widths
  for one prefill chunk and one decode step at the cell's batch.
"""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench.reference.decoder import HI  # noqa: E402
from bench.reference.mla_moe import (forward, held_margin, hidden,  # noqa: E402
                                     routing, served_gaps)
from repro.models.model import decode_init, init_params  # noqa: E402
from repro.train.steps import make_serve_step  # noqa: E402

FAMILY = harness.load_family("mla_moe_decoder")
CELL = "deepseek-v2-236b-ep8.azure-conv-b128"
CONFIG = harness.load_json(ROOT / "bench" / "configs"
                           / "deepseek-v2-236b-ep8.json")
# the registry's smoke variant at the file's keys: 8 experts in 4 groups
# of 2, each token keeping 2 groups and 2 experts; this chip holds group 1
SMOKE = dict(CONFIG, arch="deepseek-v2-236b-smoke", hidden_size=64,
             intermediate_size=128, kv_lora_rank=32, q_lora_rank=48,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             num_attention_heads=4, num_key_value_heads=4,
             moe_intermediate_size=32, n_group=4, topk_group=2,
             num_experts_per_tok=2, n_routed_experts=2, num_hidden_layers=3,
             vocab_size=256, expert_parallel=4, held_group=1,
             published={"num_hidden_layers": 3, "n_routed_experts": 8})
B, CHUNK, T = 2, 8, 24
# The float32 program is the reference's mathematics in another order of
# additions. In bf16 (weights, activations, latent cache) the logits carry
# a few percent of relative error: bf16's 2^-8 rounding grows through the
# routed scale of 16, and a near-tie of two experts' scores can route a
# token to the other expert in one of the two (2.4% read at this seed).
TOLERANCES = {"float32": 1e-4, "bfloat16": 5e-2}


def test_the_family_provides_the_whole_contract():
    cell = harness.load_cell(CELL, ROOT)
    assert cell.family.__name__ == FAMILY.__name__
    for name in harness.FAMILY_CONTRACT:
        assert callable(getattr(FAMILY, name))
    cfg = FAMILY.program_config(CONFIG)
    assert cfg.n_layers == 5 and cfg.n_experts == 160
    assert cfg.held_experts == (0, 20)
    assert (cfg.moe_n_group, cfg.moe_topk_group, cfg.moe_top_k) == (8, 3, 6)
    assert FAMILY.program_config(SMOKE).held_experts == (2, 2)


def test_a_file_whose_widths_differ_is_refused():
    with pytest.raises(SystemExit, match="not the file's"):
        FAMILY.program_config(dict(CONFIG, moe_intermediate_size=1024))
    with pytest.raises(SystemExit, match="published"):
        FAMILY.program_config(dict(CONFIG, expert_parallel=4))


def test_adapter_gives_the_program_layout():
    cfg = FAMILY.program_config(SMOKE)
    want = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    got = FAMILY.to_program(FAMILY.make_weights(SMOKE, 3), SMOKE)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, e in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == e.shape and g.dtype == e.dtype


@pytest.mark.parametrize("dtype", sorted(TOLERANCES))
def test_reference_matches_prefill_then_decode(dtype):
    cfg = dataclasses.replace(FAMILY.program_config(SMOKE),
                              dtype=jnp.dtype(dtype))
    dims = FAMILY.dims(SMOKE)
    w = FAMILY.make_weights(SMOKE, 3)
    params = jax.tree.map(lambda a: a.astype(dtype) if a.dtype == jnp.bfloat16
                          else a, FAMILY.to_program(w, SMOKE))
    step = jax.jit(make_serve_step(cfg))
    caches = decode_init(None, cfg, B, T)
    toks = np.random.default_rng(0).integers(0, dims.vocab, (B, T),
                                             dtype=np.int32)
    logits, caches = step(params, caches, toks[:, :CHUNK],
                          jnp.arange(CHUNK))
    got = [logits]
    for t in range(CHUNK, T):
        logits, caches = step(params, caches, toks[:, t:t + 1],
                              jnp.array([t]))
        got.append(logits)
    got = np.stack([np.asarray(g) for g in got], 1)     # positions 7..T-1
    for r in range(B):
        h = hidden(w, jnp.asarray(toks[r]), dims)
        ref = np.asarray(jnp.dot(h, w["lm_head"].astype(jnp.float32),
                                 precision=HI))[CHUNK - 1:]
        err = np.linalg.norm(got[r] - ref) / np.linalg.norm(ref)
        assert err < TOLERANCES[dtype], (dtype, r, err)


def test_served_gaps_reads_the_reference_best():
    dims = FAMILY.dims(SMOKE)
    w = FAMILY.make_weights(SMOKE, 4)
    toks = np.random.default_rng(1).integers(0, dims.vocab, 32,
                                             dtype=np.int32)
    logits = np.asarray(jnp.dot(hidden(w, jnp.asarray(toks), dims),
                                w["lm_head"].astype(jnp.float32),
                                precision=HI))
    start, n = 9, 6
    best = logits[start:start + n].argmax(-1).astype(np.int32)
    gap, ctrl = FAMILY.reference_gaps(w, SMOKE, toks, np.int32(start), best,
                                      n, True)
    assert np.allclose(np.asarray(gap), 0.0, atol=1e-5)
    assert (np.asarray(ctrl) >= 0).all()
    worst = logits[start:start + n].argmin(-1).astype(np.int32)
    spread = logits[start:start + n].max(-1) - logits[start:start + n].min(-1)
    gap, _ = served_gaps(w, toks, np.int32(start), worst, dims=dims,
                         n_out=n, tie=0.0)
    assert np.allclose(np.asarray(gap), spread, rtol=1e-4)
    # positions whose routing lies within the tie of deciding the held
    # experts' part read 0; the others their whole gap
    margin = np.asarray(forward(w, jnp.asarray(toks), dims)[1])[start:start + n]
    tie = float(np.median(margin))
    gap, _ = served_gaps(w, toks, np.int32(start), worst, dims=dims,
                         n_out=n, tie=tie)
    near = margin < tie
    assert near.any() and not near.all()
    assert np.allclose(np.asarray(gap), np.where(near, 0.0, spread),
                       rtol=1e-4)


@pytest.mark.parametrize("config", [CONFIG, SMOKE], ids=["cut", "smoke"])
def test_held_margin_bounds_every_change_of_the_held_experts(config):
    """Logits moved by less than half a token's margin, each, never change
    which held experts the token chooses, nor their groups' places; a move
    of the margin's size does for some token."""
    dims = FAMILY.dims(config)
    rng = np.random.default_rng(5)
    logits = rng.standard_normal((4000, dims.experts)).astype(np.float32)
    margin = np.asarray(held_margin(jnp.asarray(logits), dims))
    held = set(range(dims.first_held, dims.first_held + dims.held))

    def chosen(z):
        _, idx = routing(jnp.asarray(z), dims)
        return [held & set(row) for row in np.asarray(idx).tolist()]

    base = chosen(logits)
    assert np.isfinite(margin).all() and (margin > 0).all()
    for scale, changes in ((0.499, False), (3.0, True)):
        moved = chosen(logits + scale * margin[:, None] * rng.uniform(
            -1, 1, logits.shape).astype(np.float32))
        changed = np.array([a != b for a, b in zip(base, moved)])
        assert changed.any() == changes, (scale, changed.mean())


# -- work counts by hand, at the published widths --------------------------

D, H, QL, R, DN, DR, DV = 5120, 128, 1536, 512, 128, 64, 128
F, E, EH, K, EF, S, V = 12288, 160, 20, 6, 1536, 2, 102400
LAYERS, MOE = 5, 4
ATTN_W = D * QL + QL * H * (DN + DR) + D * R + D * DR + R * H * DN \
    + R * H * DV + H * DV * D                          # 149,225,472
PREFILL = {"kind": "prefill", "batch": 128, "q_start": 512, "q_len": 16,
           "logits_rows": 0}
DECODE = {"kind": "decode", "batch": 128, "q_start": 1100, "q_len": 1,
          "logits_rows": 128}


def _gemm_flops_by_hand(m, rows):
    # the routed projections: W_uk and W_uv run as the absorbed
    # contractions, not as GEMMs of their own
    per_layer = 2 * m * (ATTN_W - R * H * (DN + DV))
    dense = 2 * m * 3 * D * F
    moe = 2 * m * (D * E + 3 * D * S * EF)
    return LAYERS * per_layer + dense + MOE * moe + 2 * rows * D * V


@pytest.mark.parametrize("step", [PREFILL, DECODE], ids=["prefill", "decode"])
def test_work_counts_by_hand(step):
    dims = FAMILY.dims(CONFIG)
    b, q0, q, rows = (step[k] for k in ("batch", "q_start", "q_len",
                                        "logits_rows"))
    m = b * q
    gemms = FAMILY.step_gemms(dims, step)
    assert len(gemms) == 8 + 4 * 9 + (1 if rows else 0)
    assert sum(2 * mm * n * k for _, mm, n, k in gemms) \
        == _gemm_flops_by_hand(m, rows)
    assert [g for g in gemms if g[0] == "lm_head"] == (
        [("lm_head", 128, V, D)] if rows else [])
    # latent attention: (query, key) pairs causal over the real context
    pairs = q * q0 + q * (q + 1) // 2
    att_flops = 2 * b * pairs * H * (2 * R + DR) * LAYERS
    att_bytes = (b * (q0 + q) * (R + DR) * 2
                 + m * H * (2 * R + DR) * 2) * LAYERS
    assert FAMILY.attention_work(dims, step) == (att_flops, att_bytes)
    # the step: GEMMs, absorbed contractions, attention, held experts
    absorbed = 2 * 2 * m * H * DN * R * LAYERS
    experts = 2 * 3 * D * EF * m * K * EH / E * MOE      # 0.75 rows a token
    flops, nbytes = FAMILY.step_work(dims, step)
    assert flops == pytest.approx(
        _gemm_flops_by_hand(m, rows) + absorbed + att_flops + experts,
        rel=1e-12)
    touched = EH * (1 - (1 - K / E) ** m)
    weights = 2 * (LAYERS * ATTN_W + 3 * D * F + MOE * 3 * D * S * EF) \
        + 4 * D * E * MOE + 2 * 3 * D * EF * touched * MOE
    embed_head = 2 * D * (m + (V if rows else 0))
    cache = b * (q0 + q) * (R + DR) * 2 * LAYERS
    assert nbytes == pytest.approx(
        weights + embed_head + cache + 4 * rows * V, rel=1e-12)
    assert FAMILY.routed_expected(dims, step["kind"]) == (17, 1)
