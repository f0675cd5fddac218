"""The family contract: what a configuration of a new architecture brings as
files of its own, and how the harness and the metrics reach the model only
through it.

- every configuration file names a family whose module provides the whole
  contract;
- a family that lives only in this test directory is found by file name
  and runs the smoke cell end to end, with no edit to the harness;
- the dense family's work counts equal, number for number, those the
  benchmark computed before the families existed (the constants below were
  printed by `bench.flops` and `bench.scopes.attention_work` at that
  commit, for each cell's batch, at a prefill chunk and a decode step, with
  the v5e peaks).
"""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(HERE))

from bench import flops, harness  # noqa: E402
from test_bench_harness import (FAKE_PEAK, SMOKE_CONFIG,  # noqa: E402
                                smoke_cell)

PEAK = harness.load_json(ROOT / "bench" / "peaks.json")["TPU v5 lite"]

# config: [(step, step_work, GEMMs, step_gemms' roofline s, attention_work,
#           LM-head roofline s)], then routed_expected for either kind
GOLDEN = {
    "olmo-1b": ([
        ({"batch": 24, "q_start": 256, "q_len": 256, "logits_rows": 24},
         (13508724916224.0, 3968970752.0), 113, 0.06722997725059364,
         (309640298496.0, 2415919104.0), 0.0002546496703296703),
        ({"batch": 24, "q_start": 1536, "q_len": 1, "logits_rows": 24},
         (61319675904.0, 7193341952.0), 113, 0.0029209006105006103,
         (4834983936.0, 4838129664.0), 0.0002546496703296703),
    ], (7, 1)),
    "phi4-mini-3.8b": ([
        ({"batch": 16, "q_start": 256, "q_len": 256, "logits_rows": 16},
         (27027226755072.0, 8758190080.0), 225, 0.13545943851583928,
         (619280596992.0, 2684354560.0), 0.0015087833553113553),
        ({"batch": 16, "q_start": 1024, "q_len": 1, "logits_rows": 16},
         (129195048960.0, 9834029056.0), 225, 0.00944288039072039,
         (6448742400.0, 2155872256.0), 0.0015087833553113553),
    ], (7, 1)),
}


def config_files():
    return sorted((ROOT / "bench" / "configs").glob("*.json"))


@pytest.mark.parametrize("path", config_files(), ids=lambda p: p.stem)
def test_every_configuration_names_a_whole_family(path):
    config = harness.load_json(path)
    family = harness.load_family(config["family"])
    for name in harness.FAMILY_CONTRACT:
        assert callable(getattr(family, name, None)), name
    dims = family.dims(config)
    assert hash(dims) == hash(family.dims(config))
    family.program_config(config)


def test_a_family_without_the_whole_contract_is_refused(tmp_path):
    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "half.py").write_text(
        "def dims(config):\n    return ()\n")
    with pytest.raises(SystemExit, match="step_work"):
        harness.load_family("half", tmp_path)


@pytest.mark.parametrize("config", sorted(GOLDEN))
def test_dense_family_counts_as_before(config):
    cfg = harness.load_json(ROOT / "bench" / "configs" / f"{config}.json")
    family = harness.load_family(cfg["family"])
    dims = family.dims(cfg)
    steps, routed = GOLDEN[config]
    for step, work, n_gemms, gemm_s, attn, head_s in steps:
        assert family.step_work(dims, step) == work
        gemms = family.step_gemms(dims, step)
        assert len(gemms) == n_gemms
        assert flops.gemm_roofline_s(gemms, PEAK) == gemm_s
        assert family.attention_work(dims, step) == attn
        head = [g for g in gemms if g[0] == "lm_head"]
        assert flops.gemm_roofline_s(head, PEAK) == head_s
    for kind in ("prefill", "decode"):
        assert family.routed_expected(dims, kind) == routed


# -- a family found by file name in a test directory ------------------------

@pytest.fixture(scope="module")
def recorded():
    """The `recorded_decoder` family of `tests/bench/families/`, with its
    log of calls emptied."""
    family = harness.load_family("recorded_decoder", HERE)
    family.CALLS.clear()
    return family


def test_a_test_family_runs_the_smoke_cell(recorded):
    cell = smoke_cell()
    cell.config = dict(SMOKE_CONFIG, family="recorded_decoder")
    cell.family = recorded
    r = harness.run_cell(cell, seed=2 ** 33 + 11, seconds=1.5, traced=False,
                         peak=FAKE_PEAK, t_start=0.0,
                         device=harness.device_info(), log=lambda m: None)
    assert r["correct"] is True and r["failed"] == 0
    assert set(r["metrics"]) == {"setup_s", "gen_tok_s", "itl_ms_p95",
                                 "ttft_ms_p90"}
    assert {"program_config", "dims", "make_weights", "to_program",
            "reference_gaps"} <= set(recorded.CALLS)


def test_the_metrics_count_work_through_the_family(recorded):
    """Every reader of needed work gives the dense family's number through
    a family whose shape object is not `bench.flops.Dims`."""
    dense = harness.load_family("dense_decoder")
    steps = [{"kind": "prefill", "batch": 2, "q_start": 0, "q_len": 16,
              "logits_rows": 2},
             {"kind": "decode", "batch": 2, "q_start": 16, "q_len": 1,
              "logits_rows": 2}]
    trace = {"window_s": 1.0, "busy_s": 0.9, "gemm_s": 0.5,
             "steps_matched": True, "module_s": {"decode": 0.2},
             "scope_s": {"attention": 0.1, "lm_head": 0.05,
                         "unscoped": 0.2}}
    base = {"trace": trace, "steps": steps, "peak": FAKE_PEAK,
            "routed_gemms": {"prefill": (7, 1), "decode": (7, 1)}}
    names = ("step_mfu", "decode_hbm_share", "gemm_roofline",
             "attention_roofline", "lm_head_roofline", "unscoped_share")
    got = {m: harness.metric_reader(m)(dict(
        base, family=recorded, dims=recorded.dims(SMOKE_CONFIG)))
        for m in names}
    want = {m: harness.metric_reader(m)(dict(
        base, family=dense, dims=dense.dims(SMOKE_CONFIG))) for m in names}
    assert got == want and all(v is not None for v in got.values())
    assert not isinstance(recorded.dims(SMOKE_CONFIG), flops.Dims)
    assert {"step_work", "step_gemms", "routed_expected",
            "attention_work"} <= set(recorded.CALLS)
