"""From a profiler trace to the numbers the per-layer metrics read.

Two stages, so that the second can be tested on a small recorded trace:

1. `extract` reads the `.xplane.pb` the JAX profiler wrote and keeps only
   what the reduction needs, as plain lists: the device's module executions
   and op events (`[name, start_ns, duration_ns]`) per chip, and the host
   events of the harness's own spans (`bench.*`) and of program launches.
2. `reduce` aligns the device clock to the host's, clips to the traced
   window (the host span `bench.window`), and computes busy time (the union
   of op intervals), time under `dit_gemm.*` scopes, time by the model's
   scopes (`scope_s`: attention, LM head, unscoped), device time per step
   kind, and the idle gaps attributed to the host span they fall in.

Op events name HLO instructions; the scope of each (`jax.named_scope`, such
as `dit_gemm.summa`) comes from the compiled program's HLO text, whose
instructions carry `metadata={op_name="..."}` (`op_scopes`).
"""
from __future__ import annotations

import collections
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
LAUNCH = "PJRT_LoadedExecutable_Execute"
HOST_SPANS = ("bench.batch_reset", "bench.prefill", "bench.decode_step",
              "bench.token_to_host")
GEMM_SCOPE = "dit_gemm."
# the serve step's top-level scopes (docs/observability.md)
TOP_LEVEL = ("embed", "block", "final_norm", "lm_head")
SCOPE_GROUPS = ("attention", "lm_head", "unscoped")
_CONTROL = {"while", "body", "closed_call", "cond"}
_OP_NAME = re.compile(
    r'^\s*(?:ROOT\s+)?(%[\w.\-]+) = .*?metadata=\{op_name="([^"]*)"', re.M)
_PRODUCT = re.compile(
    r'^\s*(?:ROOT\s+)?%[\w.\-]+ = .*? (custom-call|dot|convolution)\(.*?'
    r'metadata=\{op_name="([^"]*)"', re.M)

Event = Tuple[str, float, float]          # name, start_ns, duration_ns


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name (`%fusion.3`) -> its `op_name` metadata."""
    return {m.group(1): m.group(2) for m in _OP_NAME.finditer(hlo_text)}


def routed_gemms(hlo_text: str) -> Tuple[int, int]:
    """Routed GEMM kernels in a compiled step: the matrix products (a Pallas
    kernel, a dot or a convolution) under `dit_gemm.*` scopes, counted
    (inside the layer loop, outside it). A scan over the layers runs the
    first count once per layer."""
    inside = outside = 0
    for m in _PRODUCT.finditer(hlo_text):
        kind, op_name = m.group(1), m.group(2)
        if GEMM_SCOPE not in op_name or (
                kind == "custom-call" and "tpu_custom_call" not in m.group(0)):
            continue
        if "while" in op_name.split(GEMM_SCOPE)[0].split("/"):
            inside += 1
        else:
            outside += 1
    return inside, outside


def instruction(event_name: str) -> str:
    """The instruction an op event names: `%mmad.60 = f32[...] ...`."""
    return event_name.split(" = ", 1)[0]


def extract(xplane_path: str) -> dict:
    """The device and host events of one trace, as plain lists."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    chips, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            chips.append({
                name: [[e.name, e.start_ns, e.duration_ns]
                       for e in lines[line].events] if line in lines else []
                for name, line in (("modules", "XLA Modules"),
                                   ("ops", "XLA Ops"))})
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in ln.events
                            if e.name.startswith("bench.")
                            or e.name == LAUNCH)
    host.sort(key=lambda e: e[1])
    return {"chips": chips, "host": host}


def union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    """Merge [start, end) intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def leaves(ops: Sequence[Event]) -> List[Event]:
    """Ops that contain no other op. A `while` or `call` op spans the ops of
    its body on the same line, so only leaves may be summed."""
    evs = sorted(ops, key=lambda e: (e[1], -e[2]))
    return [e for i, e in enumerate(evs)
            if not (i + 1 < len(evs) and evs[i + 1][1] < e[1] + e[2])]


def clock_offset(modules: Sequence[Event], host: Sequence[Event]) -> float:
    """Nanoseconds to add to device times to put them on the host clock.

    A program cannot start on the device before the host launched it, so
    the offset is the least that puts every module execution after its
    launch (the k-th launch with the k-th module). 0 when the counts differ.
    """
    launches = [h for h in host if h[0] == LAUNCH]
    if not modules or len(launches) != len(modules):
        return 0.0
    return max(0.0, max(h[1] - m[1] for h, m in zip(launches, modules)))


def scope_groups(op_name: Optional[str]) -> List[str]:
    """The `scope_s` groups an op's time counts in: `attention` (a path
    segment starting with `pattn.`), `lm_head` (a segment equal to
    `lm_head`; `pmm.lm_head` alone does not match) and `unscoped` (under
    none of the top-level scopes, as an op XLA names after an argument,
    `params['embed']`, is)."""
    segs = op_name.split("/") if op_name else []
    out = []
    if any(p.startswith("pattn.") for p in segs):
        out.append("attention")
    if "lm_head" in segs:
        out.append("lm_head")
    if not any(p in TOP_LEVEL for p in segs):
        out.append("unscoped")
    return out


def scope_label(op_name: Optional[str], instr: str) -> str:
    """A short name for an op: its scope path without the step's own jit
    name and the loop plumbing, or the instruction kind if it has none."""
    if not op_name:
        return "(no scope) " + re.sub(r"[.\d]+$", "", instr.lstrip("%"))
    parts = [p for p in op_name.split("/") if p not in _CONTROL]
    if parts and parts[0].startswith("jit("):
        parts = parts[1:]
    return "/".join(parts) or op_name


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def reduce(events: dict, step_kinds: Sequence[str],
           scopes: Dict[str, Dict[str, str]], top: int = 10) -> dict:
    """The traced window's device numbers.

    `step_kinds` is the kind ('prefill' or 'decode') of each program launch
    the harness made inside the window, in order; `scopes` maps each kind to
    its program's `op_scopes`. Busy, GEMM and scope time are averaged over
    chips.
    """
    windows = [h for h in events["host"] if h[0] == WINDOW_SPAN]
    if not windows or not events["chips"]:
        return {}
    w0, w1 = windows[0][1], windows[0][1] + windows[0][2]
    spans = [h for h in events["host"] if h[0] in HOST_SPANS
             and h[1] < w1 and h[1] + h[2] > w0]
    per_chip = []
    for chip in events["chips"]:
        mods = sorted(chip["modules"], key=lambda e: e[1])
        off = clock_offset(mods, [h for h in events["host"] if h[0] == LAUNCH])
        mods = [(n, s + off, d) for n, s, d in mods
                if w0 <= s + off < w1]
        ops = [(n, s + off, d) for n, s, d in chip["ops"]
               if w0 <= s + off < w1]
        per_chip.append(_reduce_chip(mods, ops, step_kinds, scopes, spans,
                                     w0, w1, top))
    out = dict(per_chip[0])
    n = len(per_chip)
    for key in ("busy_s", "gemm_s"):
        out[key] = sum(c[key] for c in per_chip) / n
    out["scope_s"] = {k: sum(c["scope_s"][k] for c in per_chip) / n
                      for k in SCOPE_GROUPS}
    out["chips"] = n
    return out


def _reduce_chip(mods, ops, step_kinds, scopes, spans, w0, w1, top) -> dict:
    busy = union([(s, min(s + d, w1)) for _, s, d in ops])
    busy_ns = sum(e - s for s, e in busy)
    kinds = list(step_kinds) if len(step_kinds) == len(mods) else None
    module_ns: Dict[str, float] = collections.Counter()
    bounds = []
    for i, (_, s, d) in enumerate(mods):
        kind = kinds[i] if kinds else None
        module_ns[kind or "unknown"] += d
        bounds.append((s, s + d, kind))
    by_label: Dict[str, float] = collections.Counter()
    scope_ns = dict.fromkeys(SCOPE_GROUPS, 0.0)
    gemm_ns = 0.0
    j = 0
    for name, s, d in sorted(leaves(ops), key=lambda e: e[1]):
        while j + 1 < len(bounds) and s >= bounds[j][1]:
            j += 1
        kind = bounds[j][2] if bounds and bounds[j][0] <= s else None
        instr = instruction(name)
        op_name = scopes.get(kind, {}).get(instr) if kind else None
        if op_name and GEMM_SCOPE in op_name:
            gemm_ns += d
        for group in scope_groups(op_name):
            scope_ns[group] += d
        by_label[scope_label(op_name, instr)] += d
    idle: Dict[str, float] = collections.Counter()
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    for g0, g1 in zip(edges[::2], edges[1::2]):
        if g1 <= g0:
            continue
        best, name = 0.0, "(no harness span)"
        for h, hs, hd in spans:
            ov = _overlap(g0, g1, hs, hs + hd)
            if ov > best:
                best, name = ov, h
        idle[name] += g1 - g0
    ns = 1e-9
    return {
        "window_s": (w1 - w0) * ns,
        "busy_s": busy_ns * ns,
        "gemm_s": gemm_ns * ns,
        "scope_s": {k: v * ns for k, v in scope_ns.items()},
        "modules": len(mods),
        "steps_matched": kinds is not None,
        "module_s": {k: v * ns for k, v in module_ns.items()},
        "device_ops": [[k, v * ns] for k, v in
                       sorted(by_label.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v * ns] for k, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:top]],
    }
