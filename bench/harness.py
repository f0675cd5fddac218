"""The benchmark harness: one cell of BENCHMARK.json, end to end.

Everything that belongs to one configuration, traffic mix or metric sits in
a file of its own and is found by the name BENCHMARK.json gives:

    bench/configs/<config>.json    published sizes, departures, deployment,
                                   and the family that knows the model
    bench/families/<family>.py     the model's program configuration, shape
                                   object, weights, reference and needed
                                   work (`FAMILY_CONTRACT`)
    bench/traffic/<mix>.json       batch, prompt/answer length cycles,
                                   max_len, prefill chunk, trace slice
    bench/metrics/<metric>.py      `read(run) -> float | None`
    bench/limits/<cell>.json       the limit of each number compared

A run: random weights from the seed (the family's), the planner's
warm-up on a fresh plan cache, one jitted serve step compiled for the
cell's two shapes (prefill chunk (B, C) and decode (B, 1)) with the cache
donated, a warm-up batch; then a window of lockstep batches in a closed
loop, each token copied to the host as it is produced; then the served
tokens of one finished sequence for every row of the batch checked against
the family's plain reference. The harness knows no architecture: what it
needs of the model it asks the cell's family.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import resource
import shutil
import statistics
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from bench import reduce as red
from repro.launch import serve
from repro.launch.mesh import host_mesh_grid, make_host_mesh
from repro.models import shard_ctx
from repro.models.model import decode_init
from repro.train.steps import make_serve_step

ROOT = Path(__file__).resolve().parents[1]
BENCH_DIR = ROOT / "bench"
RESULTS = ROOT / "results" / "bench"
MAX_PLAN_CANDIDATES = 12
# What a family module provides (bench/families/<family>.py):
#   program_config(config) -> the registry ModelConfig, checked (and cut) by
#       the file;  dims(config) -> a hashable shape object;
#   make_weights(config, seed, sharding=None), to_program(w, config);
#   reference_gaps(w, config, tokens, start, served, n_out, control)
#       -> (served-token gaps, float8 control's gaps), each (n_out,);
#   for one logged step dict (`Server.steps`: kind, batch, q_start, q_len,
#   logits_rows): step_work(dims, step) -> (FLOPs, bytes),
#       step_gemms(dims, step) -> [(tag, M, N, K)], the LM head's tagged
#       `lm_head`;  attention_work(dims, step) -> (FLOPs, bytes);
#   routed_expected(dims, kind) -> routed GEMM kernels (inside the layer
#       loop, outside it) a step of `kind` compiles, the head among those
#       outside.
FAMILY_CONTRACT = ("program_config", "dims", "make_weights", "to_program",
                   "reference_gaps", "step_work", "step_gemms",
                   "attention_work", "routed_expected")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One entry of BENCHMARK.json's `workloads`, with its files read."""
    name: str
    config: dict
    family: ModuleType
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(work)}")
    w = work[name]
    bench = root / "bench"

    def applies(metric: dict) -> bool:
        return "workloads" not in metric or name in metric["workloads"]

    config = load_json(bench / "configs" / f"{w['config']}.json")
    return Cell(name=name, config=config,
                family=load_family(config["family"], bench),
                traffic=load_json(bench / "traffic" / f"{w['traffic']}.json"),
                limits=load_json(bench / "limits" / f"{name}.json"),
                end_to_end=[m for m in spec["end_to_end"] if applies(m)],
                per_layer=[m for m in spec["per_layer"] if applies(m)])


def _module(path: Path, kind: str) -> ModuleType:
    """The Python file at `path`, loaded as module `bench_<kind>_<stem>`."""
    name = f"bench_{kind}_" + path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    # registered before it runs: a dataclass defined in the file looks its
    # module up there
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench: Path = BENCH_DIR) -> Callable:
    """`read` of bench/metrics/<name>.py."""
    return _module(bench / "metrics" / f"{name}.py", "metric").read


def load_family(name: str, bench: Path = BENCH_DIR) -> ModuleType:
    """bench/families/<name>.py, refused unless it provides the whole
    `FAMILY_CONTRACT`."""
    path = bench / "families" / f"{name}.py"
    mod = _module(path, "family")
    missing = [f for f in FAMILY_CONTRACT
               if not callable(getattr(mod, f, None))]
    if missing:
        raise SystemExit(f"{path}: the family lacks {missing}")
    return mod


span = jax.profiler.TraceAnnotation


class CompileCounter:
    """Counts JAX compile events while `on` (a process-wide listener)."""

    def __init__(self):
        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **_) -> None:
        if self.on and event.startswith("/jax/core/compile"):
            self.n += 1


class Server:
    """The routed serve step with its weights and one donated KV cache."""

    def __init__(self, cfg, params, batch: int, chunk: int, max_len: int,
                 mesh):
        self.rep = NamedSharding(mesh, PartitionSpec())
        self.params, self.batch, self.chunk = params, batch, chunk
        serve = make_serve_step(cfg)

        def bench_serve_step(params, caches, tokens, positions):
            logits, caches = serve(params, caches, tokens, positions)
            return jnp.argmax(logits, -1).astype(jnp.int32)[:, None], caches

        step = jax.jit(bench_serve_step, donate_argnums=(1,))
        self.caches = jax.jit(lambda: decode_init(None, cfg, batch, max_len),
                              out_shardings=self.rep)()
        self.execs, self.hlo = {}, {}
        for kind, n in (("prefill", chunk), ("decode", 1)):
            args = (params, self.caches,
                    self.put(np.zeros((batch, n), np.int32)),
                    self.put(np.arange(n, dtype=np.int32)))
            self.execs[kind] = step.lower(*args).compile()
            self.hlo[kind] = self.execs[kind].as_text()
        self.routed = {k: red.routed_gemms(h) for k, h in self.hlo.items()}
        self.steps: List[dict] = []

    def put(self, x: np.ndarray):
        return jax.device_put(x, self.rep)

    def reset(self) -> None:
        """Reuse the cache for a new batch: every layer's write index to 0
        (positions past it are masked, then overwritten)."""
        def zero(path, x):
            last = path[-1]
            if getattr(last, "key", None) == "index":
                return self.put(np.zeros(x.shape, x.dtype))
            return x
        self.caches = jax.tree_util.tree_map_with_path(zero, self.caches)

    def prefill(self, prompt: np.ndarray):
        """Dispatch the prompt in chunks; the first token (device)."""
        n = prompt.shape[1]
        tok = None
        for c in range(0, n, self.chunk):
            tok, self.caches = self.execs["prefill"](
                self.params, self.caches,
                self.put(prompt[:, c:c + self.chunk]),
                self.put(np.arange(c, c + self.chunk, dtype=np.int32)))
            self.steps.append({"kind": "prefill", "batch": self.batch,
                               "q_start": c, "q_len": self.chunk,
                               "logits_rows": self.batch
                               if c + self.chunk >= n else 0})
        return tok

    def decode(self, tok, position: int):
        """Dispatch one decode step at `position`; the next token (device)."""
        tok, self.caches = self.execs["decode"](
            self.params, self.caches, tok,
            self.put(np.array([position], np.int32)))
        self.steps.append({"kind": "decode", "batch": self.batch,
                           "q_start": position, "q_len": 1,
                           "logits_rows": self.batch})
        return tok


class TraceSlice:
    """The profiler over the first `seconds` of the window."""

    def __init__(self, out_dir: Path, seconds: float):
        self.dir, self.seconds = out_dir, seconds
        self.active = False
        self.steps = (0, 0)

    def start(self, server: Server) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.window = span("bench.window")
        self.window.__enter__()
        self.first = len(server.steps)
        self.active = True

    def maybe_stop(self, server: Server, elapsed: float, pending) -> None:
        if self.active and elapsed >= self.seconds:
            self.stop(server, pending)

    def stop(self, server: Server, pending) -> None:
        if not self.active:
            return
        if pending is not None:
            pending.block_until_ready()
        self.window.__exit__(None, None, None)
        self.steps = (self.first, len(server.steps))
        jax.profiler.stop_trace()
        self.active = False

    def xplane(self) -> Optional[str]:
        found = sorted(self.dir.glob("plugins/profile/*/*.xplane.pb"))
        return str(found[-1]) if found else None


def serve_window(server: Server, traffic: dict, vocab: int, seed: int,
                 seconds: float, trace: Optional[TraceSlice] = None) -> dict:
    """Lockstep batches back to back for `seconds`, from a batch boundary.

    Prompt and answer lengths follow the traffic file's cycles; the seed
    draws the token ids. The step after the current token is dispatched
    before that token is copied to the host, so the device has work while
    the host waits. The window is `seconds` long: the tokens that reach the
    host by its end count, and the loop stops at the first token after it.
    The batch then in flight is not checked. Every sequence of a batch has
    the batch's time to first token. `rec["worst_gap"]` splits the longest
    token gap into the host's dispatch of the next step and the wait for
    the token: (gap, dispatch, wait) in seconds.
    """
    b = traffic["batch"]
    prompts, answers = traffic["prompt_cycle"], traffic["answer_cycle"]
    rng = np.random.default_rng(seed)
    rec = {"batches": 0, "sequences": 0, "tokens": 0, "itl_s": [],
           "ttft_s": [], "finished": [], "worst_gap": (0.0, 0.0, 0.0)}
    if trace is not None:
        trace.start(server)
    t0 = last = time.perf_counter()
    j, open_ = 0, True
    while open_:
        p, g = prompts[j % len(prompts)], answers[j % len(answers)]
        j += 1
        with span("bench.batch_reset"):
            prompt = rng.integers(0, vocab, (b, p), dtype=np.int32)
            server.reset()
        start = time.perf_counter()
        rec["batches"] += 1
        rec["sequences"] += b
        with span("bench.prefill"):
            tok = server.prefill(prompt)
        served = np.zeros((b, g), np.int32)
        for i in range(g):
            nxt = None
            t_dispatch = time.perf_counter()
            if i + 1 < g:
                with span("bench.decode_step"):
                    nxt = server.decode(tok, p + i)
            t_wait = time.perf_counter()
            with span("bench.token_to_host"):
                served[:, i] = np.asarray(tok)[:, 0]
            now = time.perf_counter()
            if i == 0:
                rec["ttft_s"].extend([now - start] * b)
            else:
                rec["itl_s"].append(now - last)
                if now - last > rec["worst_gap"][0]:
                    rec["worst_gap"] = (now - last, t_wait - t_dispatch,
                                        now - t_wait)
            last = now
            if now - t0 <= seconds:
                rec["tokens"] += b
            if trace is not None:
                trace.maybe_stop(server, now - t0, nxt)
            if now - t0 >= seconds:
                open_ = False
                if nxt is not None:
                    nxt.block_until_ready()
                if i + 1 == g:
                    rec["finished"].append((prompt, served))
                break
            tok = nxt
        else:
            rec["finished"].append((prompt, served))
    if trace is not None:
        trace.stop(server, None)
    rec["window_s"] = seconds
    rec["end_s"] = last - t0
    return rec


def sample_sequences(finished, seed: int):
    """One finished sequence for every row of the batch, so that a fault in
    any row is seen: row r from a finished batch drawn from the seed, and
    one row, drawn too, from one of the longest batches.
    [(prompt row, served row)]."""
    if not finished:
        return []
    rng = np.random.default_rng([seed, 1])
    size = [p.shape[1] + s.shape[1] for p, s in finished]
    longest = [i for i, n in enumerate(size) if n == max(size)]
    rows = finished[0][0].shape[0]
    pick = rng.integers(len(finished), size=rows)
    pick[int(rng.integers(rows))] = longest[int(rng.integers(len(longest)))]
    return [(finished[i][0][r], finished[i][1][r])
            for r, i in enumerate(pick)]


def judge(gaps, limit: float):
    """`correct`, the widest gap and the number of sequences over `limit`,
    from each checked sequence's served-token gaps."""
    worst = max((float(g.max()) for g in gaps if g.size), default=None)
    failed = sum(1 for g in gaps if g.size and float(g.max()) > limit)
    return worst is not None and worst <= limit, worst, failed


def reference_gaps(cell: Cell, seed: int, seqs,
                   control: bool = False) -> List[tuple]:
    """Gap of each served token below the family's reference's best logit,
    per sequence, with the float8 control's gaps beside it (zeros unless
    `control`): [(gaps, control's gaps)]."""
    fam, config, traffic = cell.family, cell.config, cell.traffic
    w = fam.make_weights(config, seed)
    n_out = max(traffic["answer_cycle"])
    t_pad = -(-(max(traffic["prompt_cycle"]) + n_out) // 128) * 128
    out = []
    for prompt, served in seqs:
        toks = np.zeros(t_pad, np.int32)
        seq = np.concatenate([prompt, served])
        toks[:len(seq)] = seq
        pad = np.zeros(n_out, np.int32)
        pad[:len(served)] = served
        gap, ctrl = fam.reference_gaps(w, config, toks,
                                       np.int32(len(prompt) - 1), pad,
                                       n_out, control)
        g = len(served)
        out.append((np.asarray(gap)[:g], np.asarray(ctrl)[:g]))
    return out


def percentile(values, q: int) -> Optional[float]:
    """The q-th percentile (`statistics.quantiles`, n=100, inclusive)."""
    if len(values) < 2:
        return None
    return statistics.quantiles(values, n=100, method="inclusive")[q - 1]


@dataclasses.dataclass
class Setup:
    server: Server
    phases: Dict[str, float]


def set_up(cfg, cell: Cell, seed: int, plan_dir: Path) -> Setup:
    """Weights, plan warm-up, compile, warm-up batch: each phase timed."""
    tr = cell.traffic
    phases = {}
    mesh, grid = make_host_mesh(), host_mesh_grid()
    t = time.perf_counter()
    fam = cell.family
    w = fam.make_weights(cell.config, seed,
                         NamedSharding(mesh, PartitionSpec()))
    params = fam.to_program(w, cell.config)
    jax.block_until_ready(params)
    phases["weights_s"] = time.perf_counter() - t
    t = time.perf_counter()
    shutil.rmtree(plan_dir, ignore_errors=True)
    plan_dir.mkdir(parents=True)
    planner = serve.warm_plan_cache(cfg, tr["batch"], tr["chunk"],
                                    tr["max_len"], str(plan_dir), grid,
                                    MAX_PLAN_CANDIDATES)
    serve.install_gemm_context(planner, mesh)
    phases["plan_warmup_s"] = time.perf_counter() - t
    t = time.perf_counter()
    server = Server(cfg, params, tr["batch"], tr["chunk"], tr["max_len"],
                    mesh)
    phases["compile_s"] = time.perf_counter() - t
    t = time.perf_counter()
    prompt = np.zeros((tr["batch"], tr["chunk"]), np.int32)
    tok = server.prefill(prompt)
    for i in range(2):
        tok = server.decode(tok, tr["chunk"] + i)
    np.asarray(tok)
    server.reset()
    server.steps.clear()
    phases["warmup_s"] = time.perf_counter() - t
    return Setup(server, phases)


def release(setup: Setup) -> None:
    """Free the program's weights, cache and compiled programs, and take
    its routing context down, before the reference runs."""
    shard_ctx.set_gemm_context(None)
    setup.server.params = setup.server.caches = None
    setup.server = None
    gc.collect()
    jax.clear_caches()


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def host_counters() -> Dict[str, float]:
    """This process's context switches, page faults and CPU seconds, the
    host's stolen CPU time and the CPU throttling of this process's control
    group, to read the host's share in a slow window (differences over the
    window)."""
    r = resource.getrusage(resource.RUSAGE_SELF)
    out = {"cpu_s": r.ru_utime + r.ru_stime, "vol_switches": r.ru_nvcsw,
           "invol_switches": r.ru_nivcsw, "major_faults": r.ru_majflt}
    try:
        with open("/proc/stat") as f:
            cpu = f.readline().split()
        out["steal_s"] = int(cpu[8]) / os.sysconf("SC_CLK_TCK")
        with open("/sys/fs/cgroup/cpu.stat") as f:
            stat = dict(line.split() for line in f)
        out["throttled_s"] = int(stat["throttled_usec"]) * 1e-6
    except (OSError, IndexError, KeyError, ValueError):
        pass
    return out


def memory_peak_bytes() -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             peak: dict, t_start: float, device: dict,
             log: Callable[[str], None] = print) -> dict:
    """One run of `cell`: the result object of the last output line."""
    cfg = cell.family.program_config(cell.config)
    tr = cell.traffic
    out_dir = RESULTS / cell.name
    counter = CompileCounter()
    log(f"set-up phases begin at {time.perf_counter() - t_start:.3f}s")
    setup = set_up(cfg, cell, seed, out_dir / "plan_cache")
    server = setup.server
    for k, v in setup.phases.items():
        log(f"setup {k} {v:.3f}")
    log(f"shapes compiled: prefill ({tr['batch']}, {tr['chunk']}), "
        f"decode ({tr['batch']}, 1)")
    trace = (TraceSlice(out_dir / "trace", tr["trace_seconds"])
             if traced else None)
    counter.on = True
    setup_s = time.perf_counter() - t_start
    log(f"setup_s {setup_s:.3f}")
    log(f"routed GEMM kernels (in the layer, outside): {server.routed}")
    host = host_counters()
    rec = serve_window(server, tr, cell.config["vocab_size"], seed, seconds,
                       trace)
    counter.on = False
    host = {k: round(v - host[k], 3) for k, v in host_counters().items()}
    device = dict(device, memory_peak_bytes=memory_peak_bytes())
    log(f"window {rec['window_s']:.3f}s (last token at {rec['end_s']:.3f}s): "
        f"{rec['batches']} batches, {rec['sequences']} sequences, "
        f"{rec['tokens']} tokens in the window, {len(rec['finished'])} "
        f"batches finished; compiles inside the window: {counter.n}; "
        f"peak HBM {device['memory_peak_bytes']}")
    gap, dispatch, wait = (x * 1e3 for x in rec["worst_gap"])
    log(f"longest token gaps (ms): "
        f"{[round(g * 1e3, 2) for g in sorted(rec['itl_s'])[-5:]]}; the "
        f"longest {gap:.2f} = dispatch {dispatch:.2f} + wait {wait:.2f}; "
        f"in the window: {host}")

    run = {"setup_s": setup_s, "setup": setup.phases,
           "window_s": rec["window_s"], "tokens": rec["tokens"],
           "itl_s": rec["itl_s"], "ttft_s": rec["ttft_s"],
           "family": cell.family, "dims": cell.family.dims(cell.config),
           "peak": peak, "routed_gemms": server.routed, "trace": None,
           "steps": []}
    breakdown = None
    if trace is not None:
        path = trace.xplane()
        steps = server.steps[trace.steps[0]:trace.steps[1]]
        run["steps"] = steps
        if path:
            events = red.extract(path)
            kinds = [s["kind"] for s in steps]
            scopes = {k: red.op_scopes(v) for k, v in server.hlo.items()}
            run["trace"] = red.reduce(events, kinds, scopes)
        if run["trace"]:
            t = run["trace"]
            device.update(busy_s=t["busy_s"], window_s=t["window_s"])
            breakdown = {"device_ops": t["device_ops"],
                         "idle_gaps": t["idle_gaps"]}
            log(f"trace: {len(steps)} steps, {t['modules']} programs run, "
                f"busy {t['busy_s']:.6f}s of {t['window_s']:.6f}s")

    release(setup)
    server = None

    seqs = sample_sequences(rec["finished"], seed)
    t = time.perf_counter()
    gaps = reference_gaps(cell, seed, seqs)
    limit = cell.limits["logit_gap_max"]["limit"]
    correct, worst, failed = judge([g for g, _ in gaps], limit)
    log(f"checked {len(seqs)} sequences, {sum(g.size for g, _ in gaps)} "
        f"served tokens against the reference in "
        f"{time.perf_counter() - t:.1f}s")

    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = metric_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": correct, "attempted": rec["sequences"],
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {"logit_gap_max": {"value": worst, "limit": limit}}
    return result
