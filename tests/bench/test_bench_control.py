"""The served-token check has to fail what it is there to catch, shown on
the CPU at smoke width with the chip check skipped:

- the control, the reference computed with float8 GEMM operands in the
  program's place, reads a far wider gap than the program, on three seeds;
- a run whose timed path is broken underneath comes out not correct: a
  token altered where it is produced, a step whose cache state does not
  advance, half of the batch left out. (The cells run on one chip, so no
  exchange between chips can be left out.)
"""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench import control, harness  # noqa: E402
from test_bench_harness import (FAKE_PEAK, SMOKE_CONFIG,  # noqa: E402
                                smoke_cell)


# long enough that a batch finishes on a loaded CPU, so there is always a
# finished sequence to check
WINDOW_S = 4.0


def long_answers_cell():
    """More served tokens than the smoke cell: each seed compares 128 of
    them, so the control's widest gap stands clear of the program's."""
    cell = smoke_cell()
    cell.traffic = dict(cell.traffic, batch=8, prompt_cycle=[16],
                        answer_cycle=[16], max_len=32)
    return cell


def test_control_reads_a_far_wider_gap_than_the_program():
    cell = long_answers_cell()
    rows = [control.readings(cell, seed, WINDOW_S, log=lambda m: None)
            for seed in (11, 12, 13)]
    limit = cell.limits["logit_gap_max"]["limit"]
    assert all(r["tokens"] == 8 * 16 for r in rows)
    assert all(r["program_correct"] and not r["control_correct"]
               for r in rows)
    assert max(r["program"] for r in rows) <= limit
    assert min(r["control"] for r in rows) > max(
        3 * limit, 3 * max(r["program"] for r in rows))


@pytest.mark.parametrize("fault", sorted(control.FAULTS))
def test_a_broken_timed_path_is_not_correct(fault):
    cell = long_answers_cell()
    with control.planted(fault, SMOKE_CONFIG["vocab_size"]):
        r = harness.run_cell(cell, seed=5, seconds=WINDOW_S, traced=False,
                             peak=FAKE_PEAK, t_start=0.0,
                             device=harness.device_info(),
                             log=lambda m: None)
    assert r["correct"] is False and r["failed"] > 0
    assert r["checks"]["logit_gap_max"]["value"] > 3 * cell.limits[
        "logit_gap_max"]["limit"]
