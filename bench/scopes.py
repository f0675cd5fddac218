#!/usr/bin/env python3
"""Device time by the program's own scopes, and the readings built on it.

The serve step names every op with `jax.named_scope` (docs/observability.md):
one top-level scope each (`embed`, `block`, `final_norm`, `lm_head`), and
inside them `pmm.<tag>` around every GEMM, `pattn.<tag>` around attention,
`kv_cache.write`, `norm` and `rope`. The layer scan's own slicing and
stacking, copies XLA inserts and the harness's `argmax` carry none of the
four: they are "unscoped".

The benchmark's reduction (`bench/reduce.py`) gives every traced run the
device seconds of its leaf ops in the three groups of
`bench.reduce.scope_groups`, as `scope_s`: `attention` (a path segment
starting with `pattn.`), `lm_head` (a segment equal to `lm_head`;
`pmm.lm_head` alone does not match) and `unscoped`. An op XLA names after
an argument (`params['embed']`) has no scope path, and counts as unscoped.
The three readers below read it from `run["trace"]["scope_s"]`, count the
needed work through the cell's family (`run["family"]`), and are the
per-layer metrics of the same names (`bench/metrics/<name>.py`):

- `attention_roofline`: the roofline time of the attention the steps need
  (the family's `attention_work`) over `scope_s["attention"]`, in percent;
- `lm_head_roofline`: the roofline time of the LM-head GEMMs for the rows
  whose logits are used (the GEMMs the family's `step_gemms` tags
  `lm_head`) over `scope_s["lm_head"]`, in percent: prefill chunks whose
  logits go unused add device time and no need;
- `unscoped_share`: `scope_s["unscoped"]` over the busy time, in percent.

This script runs one traced window of a cell on the chip and breaks its
device time down further:

    python3 bench/scopes.py --workload <cell> --seed <n> [--seconds <s>]

It prints one JSON object: the busy and window seconds, the device seconds
by step kind and part (`block/pmm.attn.q`, `block/kv_cache.write`,
`lm_head`, `unscoped`, ...), the longest unscoped ops, `scope_s` and the
three readings.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
from pathlib import Path
from typing import Dict, Optional, Sequence, Tuple

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import flops  # noqa: E402
from bench import reduce as red  # noqa: E402

FINER = ("kv_cache.write", "norm", "rope")

# (step kind, op_name or None, `reduce.scope_label`) -> device seconds
OpSeconds = Dict[Tuple[Optional[str], Optional[str], str], float]


def op_seconds(events: dict, step_kinds: Sequence[str],
               scopes: Dict[str, Dict[str, str]]) -> OpSeconds:
    """Device seconds of the traced window's leaf ops by step kind and
    `op_name`, averaged over chips. The clock alignment, the window and
    the matching of ops to programs are `bench.reduce.reduce`'s."""
    windows = [h for h in events["host"] if h[0] == red.WINDOW_SPAN]
    if not windows or not events["chips"]:
        return {}
    w0, w1 = windows[0][1], windows[0][1] + windows[0][2]
    launches = [h for h in events["host"] if h[0] == red.LAUNCH]
    out: Dict = collections.Counter()
    for chip in events["chips"]:
        mods = sorted(chip["modules"], key=lambda e: e[1])
        off = red.clock_offset(mods, launches)
        mods = [(s + off, s + off + d) for _, s, d in mods
                if w0 <= s + off < w1]
        ops = [(n, s + off, d) for n, s, d in chip["ops"]
               if w0 <= s + off < w1]
        kinds = list(step_kinds) if len(step_kinds) == len(mods) else None
        j = 0
        for name, s, d in sorted(red.leaves(ops), key=lambda e: e[1]):
            while j + 1 < len(mods) and s >= mods[j][1]:
                j += 1
            kind = kinds[j] if kinds and mods[j][0] <= s else None
            instr = red.instruction(name)
            op_name = scopes.get(kind, {}).get(instr) if kind else None
            out[(kind, op_name, red.scope_label(op_name, instr))] += (
                d * 1e-9 / len(events["chips"]))
    return dict(out)


def part(op_name: Optional[str]) -> str:
    """The top-level scope of an op and the finest named part inside it
    (`block/pmm.attn.q`, `lm_head/pmm.lm_head`, `block`), or `unscoped`."""
    segs = op_name.split("/") if op_name else []
    top = next((p for p in segs if p in red.TOP_LEVEL), None)
    if top is None:
        return "unscoped"
    inner = next((p for p in segs if p.startswith(("pmm.", "pattn."))
                  or p in FINER), None)
    return f"{top}/{inner}" if inner else top


def _scope_s(run: dict, key: str) -> Optional[float]:
    t = run.get("trace")
    return (t or {}).get("scope_s", {}).get(key) or None


def attention_roofline(run: dict) -> Optional[float]:
    secs = _scope_s(run, "attention")
    if not secs or not run["steps"]:
        return None
    work = run["family"].attention_work
    need = sum(flops.roofline_s(*work(run["dims"], s), run["peak"])
               for s in run["steps"])
    return 100.0 * need / secs


def lm_head_roofline(run: dict) -> Optional[float]:
    secs = _scope_s(run, "lm_head")
    used = [s for s in run["steps"] if s["logits_rows"]]
    if not secs or not used:
        return None
    gemms = run["family"].step_gemms
    need = flops.gemm_roofline_s(
        [g for s in used for g in gemms(run["dims"], s) if g[0] == "lm_head"],
        run["peak"])
    return 100.0 * need / secs


def unscoped_share(run: dict) -> Optional[float]:
    """Reads nothing for a program that names none of its layers (every
    serve step runs the LM head, so its scope holds time where the program
    names them), rather than 100%."""
    t = run.get("trace")
    if not t or not t.get("busy_s") or not _scope_s(run, "lm_head"):
        return None
    return 100.0 * t["scope_s"]["unscoped"] / t["busy_s"]


READERS = {"attention_roofline": attention_roofline,
           "lm_head_roofline": lm_head_roofline,
           "unscoped_share": unscoped_share}


def summary(by_op: OpSeconds, trace: dict, run: dict,
            top: int = 12) -> dict:
    """What this script prints: parts by step kind, the longest unscoped
    ops, and the reduction's `scope_s` and the readings."""
    parts: Dict[str, float] = collections.Counter()
    loose: Dict[str, float] = collections.Counter()
    for (kind, op_name, label), secs in by_op.items():
        parts[f"{kind}:{part(op_name)}"] += secs
        if part(op_name) == "unscoped":
            loose[label] += secs
    run = dict(run, trace=trace)
    return {"busy_s": trace.get("busy_s"), "window_s": trace.get("window_s"),
            "steps": collections.Counter(s["kind"] for s in run["steps"]),
            "parts": dict(sorted(parts.items(), key=lambda kv: -kv[1])),
            "unscoped_ops": sorted(loose.items(), key=lambda kv: -kv[1])[:top],
            "scope_s": trace.get("scope_s"),
            "readings": {k: f(run) for k, f in READERS.items()}}


def traced_window(cell, seed: int, seconds: float, peak: dict) -> dict:
    """One traced window of `cell`, as `bench/run.py --trace 1` runs it,
    reduced by scope."""
    from bench import harness
    cfg = cell.family.program_config(cell.config)
    out_dir = harness.RESULTS / cell.name
    setup = harness.set_up(cfg, cell, seed, out_dir / "plan_cache")
    server = setup.server
    trace = harness.TraceSlice(out_dir / "trace",
                               cell.traffic["trace_seconds"])
    harness.serve_window(server, cell.traffic, cell.config["vocab_size"],
                         seed, seconds, trace)
    steps = server.steps[trace.steps[0]:trace.steps[1]]
    scopes = {k: red.op_scopes(v) for k, v in server.hlo.items()}
    path = trace.xplane()
    events = red.extract(path) if path else {"chips": [], "host": []}
    kinds = [s["kind"] for s in steps]
    run = {"steps": steps, "family": cell.family,
           "dims": cell.family.dims(cell.config), "peak": peak}
    result = summary(op_seconds(events, kinds, scopes),
                     red.reduce(events, kinds, scopes), run)
    result["routed_gemms"] = server.routed
    harness.release(setup)
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: the traffic's trace "
                         "slice, so the window ends with the trace)")
    args = ap.parse_args()
    from bench import harness
    device = harness.device_info()
    if device["platform"] != "tpu":
        sys.exit("scopes.py: no TPU; device time is measured on the chip")
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = harness.load_cell(args.workload, ROOT)
    peak = harness.load_json(ROOT / "bench" / "peaks.json")[device["kind"]]
    seconds = args.seconds or cell.traffic["trace_seconds"]
    result = traced_window(cell, args.seed, seconds, peak)
    print(json.dumps(dict(result, workload=args.workload, device=device)),
          flush=True)


if __name__ == "__main__":
    main()
