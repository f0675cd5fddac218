#!/usr/bin/env python3
"""Readings that set the limit of the served-token check.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds 20 \
        [--faults token_altered,state_unchanged,half_batch]

For each seed, in one process: the cell's set-up and a window of
`--seconds` at the cell's own load, then over the same sequences as a
benchmark run (one finished sequence for every row of the batch), the
widest gap of a served token below the reference's best logit (the
program's reading), and the widest gap of the token that the reference
computed with float8 GEMM operands puts first (the control's reading).
Both go through `harness.judge` against the cell's limit, as a run's
`correct` does. With `--faults`, each seed also runs once with each named
fault planted in the timed path (`FAULTS`), and the program's reading of
that run is judged. One JSON line per reading; the last line gives the
largest program reading, the smallest control reading and the smallest
reading of each fault. The benchmark's own runs never run any of this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import harness  # noqa: E402


def _token_altered(server, tok, position, real, vocab):
    """The next token of every row, off by one where it is produced."""
    return (real(server, tok, position) + 1) % vocab


def _state_unchanged(server, tok, position, real, vocab):
    """The step's cache comes back with its write index where it went in."""
    out = real(server, tok, position)
    server.caches = jax.tree_util.tree_map_with_path(
        lambda path, x: server.put(np.full(x.shape, position, x.dtype))
        if getattr(path[-1], "key", None) == "index" else x, server.caches)
    return out


def _half_batch(server, tok, position, real, vocab):
    """The back half of the batch left out: its rows take row 0's token."""
    out = np.array(real(server, tok, position))
    out[out.shape[0] // 2:] = out[0]
    return server.put(out)


FAULTS = {"token_altered": _token_altered,
          "state_unchanged": _state_unchanged,
          "half_batch": _half_batch}


@contextlib.contextmanager
def planted(fault: str, vocab: int):
    """Every decode step of the timed path broken by `fault`."""
    real = harness.Server.decode
    harness.Server.decode = (
        lambda self, tok, pos: FAULTS[fault](self, tok, pos, real, vocab))
    try:
        yield
    finally:
        harness.Server.decode = real


def readings(cell, seed: int, seconds: float, fault: str = None,
             log=print) -> dict:
    cfg = cell.family.program_config(cell.config)
    vocab = cell.config["vocab_size"]
    with planted(fault, vocab) if fault else contextlib.nullcontext():
        setup = harness.set_up(cfg, cell, seed,
                               harness.RESULTS / cell.name / "plan_cache")
        rec = harness.serve_window(setup.server, cell.traffic, vocab, seed,
                                   seconds)
    harness.release(setup)
    seqs = harness.sample_sequences(rec["finished"], seed)
    gaps = harness.reference_gaps(cell, seed, seqs, control=fault is None)
    limit = cell.limits["logit_gap_max"]["limit"]
    ok, worst, _ = harness.judge([g for g, _ in gaps], limit)
    out = {"seed": seed, "fault": fault, "sequences": len(seqs),
           "tokens": int(sum(g.size for g, _ in gaps)),
           "program": worst, "program_correct": ok}
    if fault is None:
        ok, out["control"], _ = harness.judge([c for _, c in gaps], limit)
        out["control_correct"] = ok
    log(json.dumps(out))
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, each run in turn")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--faults", default="",
                    help=f"comma-separated, of {sorted(FAULTS)}")
    args = ap.parse_args()
    if harness.device_info()["platform"] != "tpu":
        sys.exit("control.py: no TPU; the readings are taken on the chip")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = harness.load_cell(args.workload, ROOT)
    seeds = [int(s) for s in args.seeds.split(",")]
    faults = [f for f in args.faults.split(",") if f]
    rows = [readings(cell, s, args.seconds) for s in seeds]
    summary = {"workload": args.workload, "seeds": len(rows),
               "program_max": max(r["program"] for r in rows),
               "control_min": min(r["control"] for r in rows),
               "control_all_not_correct": not any(r["control_correct"]
                                                  for r in rows)}
    for fault in faults:
        got = [readings(cell, s, args.seconds, fault) for s in seeds[:3]]
        summary[fault + "_min"] = min(r["program"] for r in got)
        summary[fault + "_all_not_correct"] = not any(
            r["program_correct"] for r in got)
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
