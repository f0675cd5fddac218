"""The benchmark's plain reference against the program, on the CPU at smoke
width: a prefill chunk and then decode steps through the cache of the
unrouted serve step, against the reference's full forward; and the float8
control, which the served-token check has to tell apart from the program.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402
from bench.reference.decoder import HI, hidden  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.models.model import decode_init, init_params  # noqa: E402
from repro.train.steps import make_serve_step  # noqa: E402

SMOKE = {
    "olmo-1b-smoke": dict(
        family="dense_decoder", arch="olmo-1b-smoke", hidden_size=64,
        intermediate_size=160,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        vocab_size=256, rope_theta=10000.0, tie_word_embeddings=True,
        norm="nonparam_ln", norm_eps=1e-6),
    "phi4-mini-3.8b-smoke": dict(
        family="dense_decoder", arch="phi4-mini-3.8b-smoke", hidden_size=64,
        intermediate_size=160,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        vocab_size=256, rope_theta=10000.0, tie_word_embeddings=True,
        norm="rmsnorm", norm_eps=1e-6),
}
# bf16 weights, activations and cache against a float32 reference: logits
# carry a few bf16 epsilons (2^-8) of relative error after 3 layers
LOGITS_REL_TOL = 4 * 2.0 ** -8
B, CHUNK, T = 2, 8, 24


FAMILY = harness.load_family("dense_decoder")


def _setup(arch, seed=3):
    config = SMOKE[arch]
    dims = FAMILY.dims(config)
    w = FAMILY.make_weights(config, seed)
    return config, dims, w


@pytest.mark.parametrize("arch", sorted(SMOKE))
def test_adapter_gives_the_program_layout(arch):
    config, dims, w = _setup(arch)
    cfg = FAMILY.program_config(config)
    want = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    got = FAMILY.to_program(w, config)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, e in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == e.shape and g.dtype == e.dtype


@pytest.mark.parametrize("arch", sorted(SMOKE))
def test_reference_matches_prefill_then_decode(arch):
    config, dims, w = _setup(arch)
    cfg = get_config(arch)
    params = FAMILY.to_program(w, config)
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
        h = hidden(w, jnp.asarray(toks[r]), dims, config["norm"],
                   config["norm_eps"], config["rope_theta"])
        ref = np.asarray(jnp.dot(h, w["embed"].T.astype(jnp.float32),
                                 precision=HI))[CHUNK - 1:]
        err = np.linalg.norm(got[r] - ref) / np.linalg.norm(ref)
        assert err < LOGITS_REL_TOL, (arch, r, err)


@pytest.mark.parametrize("arch", sorted(SMOKE))
def test_served_gaps_reads_the_reference_best(arch):
    config, dims, w = _setup(arch)
    toks = np.random.default_rng(1).integers(0, dims.vocab, 32,
                                             dtype=np.int32)
    h = hidden(w, jnp.asarray(toks), dims, config["norm"],
               config["norm_eps"], config["rope_theta"])
    logits = np.asarray(jnp.dot(h, w["embed"].T.astype(jnp.float32),
                                precision=HI))
    start, n = 9, 6
    best = logits[start:start + n].argmax(-1).astype(np.int32)
    gap, _ = FAMILY.reference_gaps(w, config, toks, np.int32(start),
                                   best, n, False)
    assert np.allclose(np.asarray(gap), 0.0, atol=1e-5)
    worst = logits[start:start + n].argmin(-1).astype(np.int32)
    gap, _ = FAMILY.reference_gaps(w, config, toks, np.int32(start),
                                   worst, n, False)
    spread = logits[start:start + n].max(-1) - logits[start:start + n].min(-1)
    assert np.allclose(np.asarray(gap), spread, rtol=1e-4)
