"""The benchmark harness rehearsed on the CPU at smoke width.

The chip-only entry (`bench/run.py`) is checked to refuse the CPU and an
unknown device kind; the rest of a run (`bench.harness.run_cell`) is driven
here with the chip check skipped, on olmo-1b's smoke configuration.
"""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import harness  # noqa: E402

SMOKE_CONFIG = {
    "family": "dense_decoder", "arch": "olmo-1b-smoke", "hidden_size": 64,
    "intermediate_size": 160, "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "vocab_size": 256, "rope_theta": 10000.0,
    "tie_word_embeddings": True, "norm": "nonparam_ln", "norm_eps": 1e-6,
}
SMOKE_TRAFFIC = {"batch": 2, "prompt_cycle": [16, 32], "answer_cycle": [4, 6],
                 "max_len": 48, "chunk": 16, "trace_seconds": 1}
FAKE_PEAK = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def smoke_cell(**limits):
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    return harness.Cell(
        name="smoke", config=dict(SMOKE_CONFIG),
        family=harness.load_family(SMOKE_CONFIG["family"]),
        traffic=dict(SMOKE_TRAFFIC),
        limits={"logit_gap_max": {"limit": limits.get("limit", 0.05)}},
        end_to_end=spec["end_to_end"], per_layer=spec["per_layer"])


@pytest.fixture(scope="module")
def smoke_run():
    cell = smoke_cell()
    device = harness.device_info()
    return harness.run_cell(cell, seed=2 ** 33 + 7, seconds=1.5, traced=False,
                            peak=FAKE_PEAK, t_start=0.0, device=device,
                            log=lambda m: None)


def test_every_named_file_is_found():
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"], ROOT)
        assert cell.config["arch"] and cell.traffic["batch"] > 0
        assert cell.limits["logit_gap_max"]["limit"] > 0
        cell.family.program_config(cell.config)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))


def test_benchmark_json_follows_the_contract():
    spec = harness.load_json(ROOT / "BENCHMARK.json")
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert (ROOT / spec["command"][1]).is_file()
    assert all((ROOT / p).is_dir() for p in spec["paths"])
    names = ([c["name"] for c in spec["configs"]]
             + [w["name"] for w in spec["workloads"]]
             + [m["name"] for m in spec["end_to_end"] + spec["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file()
    ends = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in ends
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert m["moves"] in ends and "bound" not in m
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


def test_last_line_schema(smoke_run):
    r = smoke_run
    assert list(r)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(r)
    assert r["correct"] is True and r["failed"] == 0
    assert r["attempted"] >= 2 and r["attempted"] % 2 == 0
    assert set(r["metrics"]) == {"setup_s", "gen_tok_s", "itl_ms_p95",
                                 "ttft_ms_p90"}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        r["device"])
    check = r["checks"]["logit_gap_max"]
    assert 0 <= check["value"] <= check["limit"]
    json.dumps(r)


def test_the_check_covers_every_row_of_the_batch():
    rng = np.random.default_rng(0)
    finished = [(rng.integers(0, 9, (4, p)), rng.integers(0, 9, (4, g)))
                for p, g in ((16, 4), (32, 6), (16, 4), (32, 6))]
    for seed in (1, 2, 2 ** 33 + 5):
        seqs = harness.sample_sequences(finished, seed)
        assert len(seqs) == 4
        # row r of some finished batch, for every r
        for r, (prompt, served) in enumerate(seqs):
            assert any(np.array_equal(prompt, p[r]) and
                       np.array_equal(served, s[r]) for p, s in finished)
        assert max(len(s) for _, s in seqs) == 6
    assert harness.sample_sequences([], 1) == []


def test_judge_counts_sequences_over_the_limit():
    gaps = [np.array([0.01, 0.02]), np.array([0.5]), np.array([])]
    assert harness.judge(gaps, 0.1) == (False, 0.5, 1)
    assert harness.judge(gaps[:1], 0.1) == (True, 0.02, 0)
    assert harness.judge([], 0.1) == (False, None, 0)


def _run_py(args, env_extra=None, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, str(cwd / "bench" / "run.py")]
                          + args, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=120)


def test_run_py_refuses_the_cpu():
    p = _run_py(["--workload", "olmo-1b.azure-code", "--seed", "1",
                 "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0
    assert "no TPU" in p.stderr and "{" not in p.stdout


def test_run_py_refuses_an_unknown_device_kind(tmp_path):
    # a copy of the benchmark whose peaks table lacks the CPU's kind, and
    # whose platform check is passed by naming the CPU a chip
    import shutil
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    (tmp_path / "src").symlink_to(ROOT / "src")
    run = (tmp_path / "bench" / "run.py").read_text()
    run = run.replace('device["platform"] != "tpu"', "False")
    (tmp_path / "bench" / "run.py").write_text(run)
    p = _run_py(["--workload", "olmo-1b.azure-code", "--seed", "1",
                 "--seconds", "1", "--trace", "0"], cwd=tmp_path)
    assert p.returncode != 0
    assert "is not in bench/peaks.json" in p.stderr and "{" not in p.stdout


def test_run_py_fails_without_the_program(tmp_path):
    import shutil
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = _run_py(["--workload", "olmo-1b.azure-code", "--seed", "1",
                 "--seconds", "1", "--trace", "0"], cwd=tmp_path)
    assert p.returncode != 0 and "{" not in p.stdout


def test_traced_run_reports_per_layer_metrics_only():
    # the CPU's trace has no TPU plane: the device-trace metrics stay out
    # of the line rather than reading 0, and the host-clock one is there
    r = harness.run_cell(smoke_cell(), seed=9, seconds=1.5, traced=True,
                         peak=FAKE_PEAK, t_start=0.0,
                         device=harness.device_info(), log=lambda m: None)
    assert r["correct"] is True
    assert set(r["metrics"]) == {"plan_warmup_s"}
    assert "breakdown" not in r and "busy_s" not in r["device"]
