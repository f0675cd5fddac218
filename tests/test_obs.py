"""Observability layer: span lifecycle, Perfetto export schema, metrics
snapshots, drift monitoring, GemmStats round-trip, run-report assembly —
plus a slow multidevice subprocess proving a routed serve run emits a
complete `run_report.json` (full plan provenance, zero silent degrades)
and a loadable Chrome trace."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.core.schedule import GEMMShape
from repro.hw.config import tpu_pod_as_accelerator
from repro.models.shard_ctx import GemmStats
from repro.obs import (DRIFT_STALE_THRESHOLD, DriftMonitor, MetricsRegistry,
                       RUN_REPORT_SCHEMA_VERSION, Tracer, build_run_report,
                       describe_routing, get_tracer, render_run_report,
                       set_tracer, tracing, write_run_report)
from repro.obs.trace import CAT_PMM, CAT_STEP, maybe_span
from repro.sim.calibrate import CalibrationProfile, CalibrationSample
from repro.sim.perf import PerfReport


# ---------------------------------------------------------------------------
# tracer: span lifecycle + Chrome trace-event export
# ---------------------------------------------------------------------------

def test_span_lifecycle_records_complete_event():
    tracer = Tracer(process_name="t")
    with tracer.span("pmm.attn.q", tag="attn.q", shape=[8, 16, 32]) as args:
        args["provenance"] = "hit"
    (ev,) = tracer.events
    assert ev["ph"] == "X" and ev["cat"] == CAT_PMM
    assert ev["name"] == "pmm.attn.q"
    assert ev["dur"] >= 0 and ev["ts"] >= 0
    # mid-span provenance lands in the event args, plus the measured dur
    assert ev["args"]["provenance"] == "hit"
    assert ev["args"]["shape"] == [8, 16, 32]
    assert ev["args"]["dur_us"] == ev["dur"]


def test_span_records_even_when_body_raises():
    tracer = Tracer()
    with pytest.raises(RuntimeError):
        with tracer.span("pmm.x", tag="x"):
            raise RuntimeError("boom")
    assert len(tracer.events) == 1


def test_event_cap_drops_not_grows():
    tracer = Tracer(max_events=2)
    for i in range(5):
        with tracer.span("s", i=i):
            pass
    assert len(tracer.events) == 2 and tracer.dropped == 3
    assert tracer.to_chrome_trace()["otherData"]["dropped_events"] == 3


def test_chrome_trace_is_perfetto_loadable_schema(tmp_path):
    tracer = Tracer(process_name="serve.test")
    with tracer.span("pmm.ffn.up", cat=CAT_PMM, tag="ffn.up"):
        pass
    tracer.instant("pmm.probe", provenance="unrouted")
    path = tracer.write(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    # the Chrome trace-event envelope Perfetto's JSON importer requires
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["dropped_events"] == 0
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert meta and meta[0]["name"] == "process_name"
    assert meta[0]["args"]["name"] == "serve.test"
    for e in evs:
        assert {"name", "ph", "pid", "tid"} <= set(e)
        if e["ph"] == "X":
            assert isinstance(e["ts"], (int, float)) and e["dur"] >= 0
        if e["ph"] == "i":
            assert e["s"] == "t"
    # everything must be JSON-serializable without a custom encoder
    json.dumps(doc)


def test_global_tracer_install_and_maybe_span():
    assert get_tracer() is None
    with maybe_span("noop") as args:      # no tracer installed: a no-op
        assert args is None
    tracer = Tracer()
    with tracing(tracer):
        assert get_tracer() is tracer
        with maybe_span("serve.decode_token", position=3) as args:
            assert args is not None
    assert get_tracer() is None
    (ev,) = tracer.events
    assert ev["cat"] == CAT_STEP and ev["args"]["position"] == 3


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_metrics_snapshot():
    reg = MetricsRegistry()
    reg.counter("pmm.provenance.hit").inc()
    reg.counter("pmm.provenance.hit").inc(2)
    for v in (1.0, 2.0, 3.0, 4.0):
        reg.observe("pmm.dispatch_us.mode.summa", v)
    snap = reg.to_dict()
    assert snap["counters"] == {"pmm.provenance.hit": 3}
    h = snap["histograms"]["pmm.dispatch_us.mode.summa"]
    assert h["count"] == 4 and h["sum"] == 10.0
    assert h["min"] == 1.0 and h["max"] == 4.0 and h["mean"] == 2.5
    assert h["p50"] <= h["p95"] <= h["p99"] <= h["max"]
    json.dumps(snap)
    # the zero-observation snapshot carries the full percentile schema too
    # (the serving section's SLO accounting indexes p99 unconditionally)
    empty = MetricsRegistry().histogram("never.observed").to_dict()
    assert empty["count"] == 0
    assert {"p50", "p95", "p99"} <= set(empty)
    assert empty["p99"] == 0.0


# ---------------------------------------------------------------------------
# drift monitor
# ---------------------------------------------------------------------------

def _report(total=1e-3, steps=4) -> PerfReport:
    return PerfReport(total_time=total, compute_time=total * 0.5,
                      dma_time=total * 0.3, noc_time=total * 0.2,
                      barrier_time=0.0, total_flops=10**9, hbm_bytes=10**6,
                      noc_bytes=10**5, n_supersteps=steps)


def _samples(measured_scale: float, n=6):
    hw = tpu_pod_as_accelerator((4, 4))
    profile = CalibrationProfile.identity(hw, n_samples=n, fit_ok=True)
    samples = []
    for i in range(n):
        rep = _report(total=1e-3 * (i + 1))
        mode = "summa" if i % 2 == 0 else "cannon"
        samples.append(CalibrationSample(
            shape=(64, 64, 64), dataflow=mode, mode=mode, report=rep,
            measured_s=profile.predict(rep) * measured_scale))
    return profile, samples


def test_drift_monitor_flags_mis_scaled_profile():
    """A profile predicting 2.1x too fast trips the staleness flag."""
    profile, samples = _samples(measured_scale=2.1)
    mon = DriftMonitor(profile)
    assert mon.add_samples(samples) == len(samples)
    s = mon.summary()
    assert s["profile_stale"] is True
    assert s["drift_distance"] > DRIFT_STALE_THRESHOLD
    assert s["geomean_ratio"] == pytest.approx(2.1, rel=1e-3)
    assert set(s["per_mode"]) == {"summa", "cannon"}
    for rec in s["per_mode"].values():
        assert rec["geomean_ratio"] == pytest.approx(2.1, rel=1e-3)
    assert s["profile_digest"] == profile.digest()
    assert s["profile_trusted"] is True


def test_drift_monitor_accepts_accurate_profile():
    profile, samples = _samples(measured_scale=1.05)
    mon = DriftMonitor(profile)
    mon.add_samples(samples)
    s = mon.summary()
    assert s["profile_stale"] is False
    assert s["geomean_ratio"] == pytest.approx(1.05, rel=1e-3)


def test_drift_staleness_is_symmetric():
    """Predicting too slow is as stale as predicting too fast."""
    profile, samples = _samples(measured_scale=1 / 2.1)
    mon = DriftMonitor(profile)
    mon.add_samples(samples)
    s = mon.summary()
    assert s["profile_stale"] is True
    assert s["drift_distance"] == pytest.approx(2.1, rel=1e-3)


def test_drift_monitor_edge_cases():
    mon = DriftMonitor()
    mon.add("summa", 0.0, 1.0)          # non-positive prediction: skipped
    mon.add("summa", 1.0, -1.0)         # non-positive measurement: skipped
    assert mon.n_samples == 0
    s = mon.summary()
    assert s["profile_stale"] is False and s["n_samples"] == 0
    with pytest.raises(ValueError):
        DriftMonitor(threshold=0.5)


# ---------------------------------------------------------------------------
# GemmStats round-trip + the single-sourced routing line
# ---------------------------------------------------------------------------

def test_gemm_stats_roundtrip_and_describe():
    s = GemmStats()
    s.hits, s.bucketed, s.fallback, s.unrouted = 3, 1, 1, 2
    s.modes = {"summa": 3, "auto": 2}
    s.degrades = {"grid_mismatch": 1}
    s.silent_degrades = 0
    s.observed[("attn.q", GEMMShape(8, 16, 32))] = 4
    d = s.to_dict()
    json.dumps(d)
    assert d["calls"] == 7 and d["routed"] == 5 and d["unrouted"] == 2
    assert d["resolve_rate"] == pytest.approx(4 / 5)
    assert d["silent_degrades"] == 0
    assert d["observed"] == [{"tag": "attn.q", "shape": [8, 16, 32],
                              "count": 4}]
    # round-trip preserves the snapshot
    s2 = GemmStats.from_dict(d)
    assert s2.to_dict() == d
    # the print IS the dict: describe() delegates to describe_routing()
    assert s.describe() == describe_routing(d)
    assert "plan-resolve-rate=80%" in s.describe()


# ---------------------------------------------------------------------------
# run report: build, write, render
# ---------------------------------------------------------------------------

def test_run_report_build_write_render(tmp_path):
    tracer = Tracer(process_name="serve.t")
    with tracer.span("pmm.attn.q", cat=CAT_PMM, tag="attn.q",
                     shape=[8, 16, 32]) as args:
        args.update(provenance="hit", mode="summa", plan_digest="abc123")
    stats = {"calls": 1, "routed": 1, "hits": 1, "bucketed": 0,
             "fallback": 0, "unrouted": 0, "resolve_rate": 1.0,
             "modes": {"summa": 1}, "degrades": {}, "silent_degrades": 0,
             "observed": []}
    profile, samples = _samples(measured_scale=2.1)
    mon = DriftMonitor(profile)
    mon.add_samples(samples)
    report = build_run_report("serve", stats=stats, drift=mon.summary(),
                              tracer=tracer, extra={"arch": "t"})
    assert report["schema_version"] == RUN_REPORT_SCHEMA_VERSION
    assert report["launcher"] == "serve" and report["arch"] == "t"
    assert "workload" not in report          # None sections are omitted
    (disp,) = report["dispatches"]
    assert disp["name"] == "pmm.attn.q" and disp["provenance"] == "hit"
    assert disp["plan_digest"] == "abc123" and "dur_us" in disp

    path = str(tmp_path / "sub" / "run_report.json")
    write_run_report(path, report)
    assert json.load(open(path)) == json.loads(json.dumps(report))

    lines = render_run_report(report)
    assert any(l.startswith("plan routing: pmm calls=1") for l in lines)
    assert any("lowered modes" in l for l in lines)
    assert any("calibration drift" in l and "STALE" in l for l in lines)


def test_exec_plan_to_dict_is_jsonable():
    from repro.core.lower import lower_schedule
    from repro.core.schedule import Schedule, Tiling
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    sched = Schedule(GEMMShape(64, 64, 64), Tiling(1, 1, 1, tk=64), "summa",
                     inner=(1, 1))
    ep = lower_schedule(sched, mesh, shape=(64, 64, 64))
    d = ep.to_dict()
    json.dumps(d)
    assert d["requested"] == "summa"
    assert d["shape"] == [64, 64, 64]
    assert isinstance(d["degraded"], bool)
    assert all({"reason", "from", "to"} <= set(f) for f in d["fallbacks"])


# ---------------------------------------------------------------------------
# traced routed dispatch: provenance lands in the spans (single device)
# ---------------------------------------------------------------------------

def test_pmm_dispatch_emits_provenance_spans():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.deploy import Planner
    from repro.hw.config import (AcceleratorConfig, HBMConfig, NoCConfig,
                                 TileConfig)
    from repro.launch.mesh import make_mesh
    from repro.models import shard_ctx
    from repro.models.matmul import pmm

    mini = AcceleratorConfig(name="mini", grid=(4, 4),
                             tile=TileConfig(l1_bytes=4 * 1024 * 1024),
                             noc=NoCConfig(), hbm=HBMConfig(n_channels=8))
    planner = Planner(mini, elem_bytes=4, max_candidates=4)
    shape = GEMMShape(64, 32, 16)
    planner.batch_tune([shape])
    mesh = make_mesh((1, 1), ("data", "model"))
    ctx = shard_ctx.GemmContext(mesh=mesh, planner=planner)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)

    tracer = Tracer()
    with tracing(tracer), shard_ctx.gemm_context(ctx):
        jax.jit(lambda a, b: pmm(a, b, tag="probe")).lower(x, w)
        pmm(x, w)                        # untagged + unjitted also traced

    spans = tracer.spans(CAT_PMM)
    assert len(spans) == 2
    by_name = {e["name"]: e["args"] for e in spans}
    prov = by_name["pmm.probe"]
    assert prov["provenance"] == "hit" and prov["tag"] == "probe"
    assert prov["shape"] == [64, 32, 16]
    assert prov["plan_digest"] and prov["plan_resolve_us"] >= 0
    assert prov["predicted_s"] > 0 and prov["mode"]
    assert "pmm.untagged" in by_name
    # the dispatch metrics rode along
    snap = tracer.metrics.to_dict()
    assert snap["counters"]["pmm.provenance.hit"] == 2
    assert any(k.startswith("pmm.dispatch_us.mode.")
               for k in snap["histograms"])


def test_untraced_dispatch_unchanged():
    """No tracer installed: routing still works, nothing recorded."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import shard_ctx
    from repro.models.matmul import pmm

    ctx = shard_ctx.GemmContext(mesh=None)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    assert get_tracer() is None
    with shard_ctx.gemm_context(ctx):
        out = pmm(x, w, tag="probe")
    assert out.shape == (8, 8) and ctx.stats.unrouted == 1


# ---------------------------------------------------------------------------
# the end-to-end proof: routed serve run emits a complete run report
# (multidevice, subprocess — keeps fake devices out of this process)
# ---------------------------------------------------------------------------

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SERVE_REPORT_BODY = textwrap.dedent("""
    import json
    import subprocess
    import sys

    out = sys.argv[1]
    # plans are priced for the 16x1 host mesh: prompt 12 + gen 4 fills a
    # 16-slot cache that attention's KV can shard over the 16 rows
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "gemma-2b",
         "--smoke", "--batch", "2", "--prompt-len", "12", "--gen", "4",
         "--plan-candidates", "4", "--plan-cache", out + "/cache",
         "--run-report", out + "/run_report.json",
         "--trace", out + "/trace.json"],
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]

    r = json.load(open(out + "/run_report.json"))
    assert r["schema_version"] == 1 and r["launcher"] == "serve"
    routing = r["routing"]
    assert routing["calls"] > 0
    assert routing["calls"] == routing["routed"], routing
    assert routing["unrouted"] == 0 and routing["resolve_rate"] == 1.0
    assert routing["silent_degrades"] == 0, routing
    # every dispatch carries full plan provenance. GEMM rows come from the
    # warmed cache (tuned or bucketed plans: the warm-up covered every GEMM
    # the run executed); attention rows (pattn.*) resolve online from the
    # closed-form menu, so "analytic" joins their vocabulary, and their
    # shape is the 7-dim attention problem, not (m, n, k)
    assert r["dispatches"], "no pmm spans recorded"
    attn_rows = [d for d in r["dispatches"] if d["name"].startswith("pattn.")]
    assert attn_rows, "attention never routed through pattn"
    for d in r["dispatches"]:
        if d["name"].startswith("pattn."):
            assert d["provenance"] in ("hit", "bucketed", "analytic",
                                       "fallback"), d
            assert d["tag"] and len(d["shape"]) == 7, d
            if d["provenance"] != "fallback":
                assert d["attn_schedule"], d
        else:
            assert d["provenance"] in ("hit", "bucketed"), d
            assert d["tag"] and len(d["shape"]) == 3, d
            assert d["plan_digest"], d
        assert d["plan_resolve_us"] >= 0 and d["dur_us"] >= 0, d
    assert r["metrics"]["counters"], r["metrics"]
    # the trace next to it is a loadable Chrome trace document
    t = json.load(open(out + "/trace.json"))
    assert t["displayTimeUnit"] == "ms" and t["traceEvents"]
    cats = {e.get("cat") for e in t["traceEvents"]}
    assert {"pmm", "step"} <= cats, cats
    # the shutdown print renders from the same dict the report persists
    from repro.obs import describe_routing
    assert ("plan routing: " + describe_routing(routing)) in proc.stdout
    print("ALL_OK")
""")


@pytest.mark.slow
def test_serve_run_report_multidevice(tmp_path):
    """A routed multidevice serve run emits a complete run_report.json
    (full provenance, zero silent degrades) + a loadable Chrome trace."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    proc = subprocess.run(
        [sys.executable, "-c", SERVE_REPORT_BODY, str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (f"stdout:\n{proc.stdout}\n"
                                  f"stderr:\n{proc.stderr}")
    assert "ALL_OK" in proc.stdout


# ---------------------------------------------------------------------------
# one clock: tracer spans land in a jax.profiler capture
# ---------------------------------------------------------------------------

def test_span_lands_in_profiler_capture(tmp_path):
    """A `maybe_span` under an installed tracer is also a host event of a
    `jax.profiler` capture, on the clock the device trace is aligned to."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        with tracing(Tracer()) as tracer:
            with maybe_span("serve.probe_step", position=1):
                jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert [e["name"] for e in tracer.events] == ["serve.probe_step"]
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    host = [e for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:") for line in plane.lines
            for e in line.events if e.name == "serve.probe_step"]
    assert len(host) == 1 and host[0].duration_ns > 0


def test_obs_imports_without_jax():
    code = ("import sys, repro.obs; "
            "assert 'jax' not in sys.modules, 'repro.obs loaded jax'")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + os.environ.get("PYTHONPATH", "").split(os.pathsep)))
    subprocess.run([sys.executable, "-c", code], check=True, env=env)
