#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Exits non-zero, printing no result, when JAX finds no TPU, fewer chips
than the cell asks for, or a device kind missing from bench/peaks.json.
Earlier lines give the device, the set-up phases, the shapes compiled, the
window's batches and tokens, compiles inside the window and peak HBM. The
last line of stdout is one JSON object: `correct`, `attempted`, `failed`
(sequences), `metrics` (the cell's end-to-end metrics, or with --trace 1
its per-layer ones), `device`, with --trace 1 `breakdown`, and last
`checks`: each number compared with its limit, also printed as the last
lines of stderr.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def log(msg: str) -> None:
    print(msg, flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if args.seed < 0:
        sys.exit("run.py: --seed must be a non-negative integer")

    from bench import harness
    t_imports = time.perf_counter() - T_START
    cell = harness.load_cell(args.workload, ROOT)
    chips = next(w["chips"] for w in harness.load_json(
        ROOT / "BENCHMARK.json")["workloads"] if w["name"] == args.workload)
    peaks = harness.load_json(ROOT / "bench" / "peaks.json")

    t = time.perf_counter()
    device = harness.device_info()
    runtime_s = time.perf_counter() - t
    if device["platform"] != "tpu":
        sys.exit(f"run.py: no TPU (JAX platform {device['platform']!r}); "
                 f"this benchmark runs only on the chip")
    if device["count"] < chips:
        sys.exit(f"run.py: {args.workload} needs {chips} chips, JAX sees "
                 f"{device['count']}")
    if device["kind"] not in peaks:
        sys.exit(f"run.py: device kind {device['kind']!r} is not in "
                 f"bench/peaks.json")
    log(f"device: {device}; imports {t_imports:.3f}s, TPU runtime start "
        f"{runtime_s:.3f}s (not in setup_s)")

    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    # setup_s runs from process start to window start, less the TPU
    # runtime's own start (the first jax.devices()): no program change can
    # move that, and it swings by seconds with the previous process's exit
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), peaks[device["kind"]],
                              T_START + runtime_s, device, log=log)
    for name, check in result["checks"].items():
        print(f"check {name} {check['value']} limit {check['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
