"""Serving harness: the traffic replay's contracts stay mechanical.

- seeded trace generation is deterministic (same seed -> same trace) and
  per-tenant isolated (adding a tenant never perturbs another's arrivals);
- continuous-batcher invariants: every submitted request is admitted
  exactly once, admission order within a tenant is arrival order, the
  tenant with the oldest waiting head is always served next (no
  starvation), and bucket-mode admission ages out at `max_wait_s`;
- SLO accounting arithmetic on hand-built request records;
- the virtual-clock replay completes every request, charges cold shapes
  exactly once, and produces an identical serving section on re-run;
- hypothesis properties (function-scoped guard, same pattern as
  test_analytic.py): bucket-aware admission never emits a batch whose M is
  outside the warmed pow-2 pool; request conservation under arbitrary
  submit/drain interleavings;
- a slow multidevice subprocess proof: `serve --traffic` on routed
  gemma-2b emits a run report with resolve_rate 1.0, zero silent
  degrades, and a serving section with nonzero goodput + per-phase hit
  rates (the ISSUE's production-traffic claim, asserted end-to-end).
"""
import json
import math
import os
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import pytest

from repro.configs import smoke_config
from repro.deploy.batcher import (BatchPolicy, ContinuousBatcher, Request,
                                  bucket_pool, decode_m)
from repro.hw.config import (AcceleratorConfig, HBMConfig, NoCConfig,
                             TileConfig)
from repro.launch.traffic import (PHASES, RequestRecord, ServingCosts,
                                  TenantSpec, TrafficConfig, batch_workload,
                                  generate_trace, serving_section, simulate,
                                  slo_summary)

MINI = AcceleratorConfig(name="mini", grid=(4, 4),
                         tile=TileConfig(l1_bytes=4 * 1024 * 1024),
                         noc=NoCConfig(), hbm=HBMConfig(n_channels=8))

STUB_CFG = smoke_config("olmo-1b")


class StubPlanner:
    """plan_cached stub: pow-2 M serves a tuned hit, ragged M an analytic
    plan, and M in `unplanned` returns None (the fallback path). Cost is a
    deterministic function of the shape, so replays are reproducible."""

    def __init__(self, unplanned=()):
        self.hw = MINI
        self.elem_bytes = 4
        self.unplanned = set(unplanned)
        self.lookups = 0

    def plan_cached(self, shape):
        self.lookups += 1
        if shape.m in self.unplanned:
            return None
        source = "tuned" if shape.m & (shape.m - 1) == 0 else "analytic"
        return SimpleNamespace(
            source=source,
            report=SimpleNamespace(total_time=1e-6 * shape.m + 1e-5))


def _traffic(seed=3, n=12):
    return TrafficConfig(seed=seed, tenants=(
        TenantSpec(name="a", rate_rps=300.0, n_requests=n,
                   prompt_lens=(5, 9, 13), gen_lens=(1, 2, 3)),
        TenantSpec(name="b", rate_rps=200.0, n_requests=n,
                   prompt_lens=(7, 11), gen_lens=(1, 2)),
    ))


def _req(rid, tenant="a", arrival=0.0, prompt=8, gen=2, slo=math.inf):
    return Request(rid=rid, tenant=tenant, arrival_s=arrival,
                   prompt_len=prompt, gen_len=gen, slo_s=slo)


# ---------------------------------------------------------------------------
# seeded trace generation
# ---------------------------------------------------------------------------

def _key(r):
    return (r.rid, r.tenant, r.arrival_s, r.prompt_len, r.gen_len, r.slo_s)


def test_generate_trace_deterministic():
    a = [_key(r) for r in generate_trace(_traffic(seed=3))]
    b = [_key(r) for r in generate_trace(_traffic(seed=3))]
    assert a == b
    c = [_key(r) for r in generate_trace(_traffic(seed=4))]
    assert a != c


def test_generate_trace_tenant_isolation():
    """Adding a tenant must not perturb another tenant's stream — each
    tenant draws from its own seeded RNG."""
    solo = TrafficConfig(seed=3, tenants=(_traffic().tenants[0],))
    both = _traffic(seed=3)
    solo_a = [(r.arrival_s, r.prompt_len, r.gen_len)
              for r in generate_trace(solo) if r.tenant == "a"]
    both_a = [(r.arrival_s, r.prompt_len, r.gen_len)
              for r in generate_trace(both) if r.tenant == "a"]
    assert solo_a == both_a


def test_generate_trace_sorted_and_bounded():
    trace = generate_trace(_traffic())
    assert [r.rid for r in trace] == list(range(len(trace)))
    arrivals = [r.arrival_s for r in trace]
    assert arrivals == sorted(arrivals)
    for r in trace:
        spec = {"a": _traffic().tenants[0], "b": _traffic().tenants[1]}
        assert r.prompt_len in spec[r.tenant].prompt_lens
        assert r.gen_len in spec[r.tenant].gen_lens
        assert r.slo_s == (spec[r.tenant].slo_ttft_s
                           + r.gen_len * spec[r.tenant].slo_per_token_s)


# ---------------------------------------------------------------------------
# batcher invariants
# ---------------------------------------------------------------------------

def _drain(batcher, now=1e9):
    batches = []
    while True:
        b = batcher.next_prefill(now)
        if b is None:
            break
        batches.append(b)
    return batches


@pytest.mark.parametrize("mode", ["bucket", "fifo"])
def test_batcher_conservation_and_fifo_order(mode):
    batcher = ContinuousBatcher(BatchPolicy(mode=mode))
    reqs = [_req(i, tenant="ab"[i % 2], arrival=0.001 * i, prompt=5 + i)
            for i in range(13)]
    for r in reqs:
        batcher.submit(r)
    batches = _drain(batcher)
    admitted = [r.rid for b in batches for r in b.requests]
    assert sorted(admitted) == [r.rid for r in reqs]     # exactly once
    assert batcher.pending() == 0
    assert batcher.admitted == batcher.submitted == len(reqs)
    for tenant in ("a", "b"):
        order = [r.rid for b in batches for r in b.requests
                 if r.tenant == tenant]
        assert order == sorted(order), "FIFO order broken within tenant"


def test_batcher_no_starvation_oldest_head_first():
    batcher = ContinuousBatcher(BatchPolicy(mode="bucket"))
    # tenant b's lone request is OLDER than tenant a's flood
    batcher.submit(_req(0, tenant="b", arrival=0.0, prompt=3))
    for i in range(1, 9):
        batcher.submit(_req(i, tenant="a", arrival=0.5, prompt=16))
    first = batcher.next_prefill(now=10.0)
    assert first.tenant == "b" and first.requests[0].rid == 0


def test_bucket_admission_waits_then_ages_out():
    policy = BatchPolicy(mode="bucket", min_fill=0.75, max_wait_s=0.05)
    batcher = ContinuousBatcher(policy)
    batcher.submit(_req(0, arrival=1.0, prompt=5))   # 5/8 = 0.625 < 0.75
    assert batcher.next_prefill(now=1.0) is None     # waits for fill
    assert batcher.next_decision_s() == pytest.approx(1.05)
    aged = batcher.next_prefill(now=1.05)            # aging bound reached
    assert aged is not None and aged.rows == 5 and aged.m == 8


def test_bucket_admission_prefers_best_fill():
    """6+2 fills the 8-bucket exactly; the third request would spill to 16
    at 11/16 fill — admission stops at the full bucket."""
    batcher = ContinuousBatcher(BatchPolicy(mode="bucket"))
    for i, p in enumerate((6, 2, 3)):
        batcher.submit(_req(i, arrival=0.0, prompt=p))
    batch = batcher.next_prefill(now=0.0)
    assert [r.rid for r in batch.requests] == [0, 1]
    assert batch.rows == 8 and batch.m == 8 and batch.utilization == 1.0


def test_fifo_admission_is_exact_and_immediate():
    batcher = ContinuousBatcher(BatchPolicy(mode="fifo"))
    batcher.submit(_req(0, arrival=0.0, prompt=5))
    batch = batcher.next_prefill(now=0.0)             # no waiting
    assert batch.rows == 5 and batch.m == 5           # no padding


def test_decode_m_and_bucket_pool():
    bucket = BatchPolicy(mode="bucket")
    fifo = BatchPolicy(mode="fifo")
    assert decode_m(3, bucket) == 4 and decode_m(3, fifo) == 3
    assert decode_m(8, bucket) == 8
    assert bucket_pool(40, bucket) == [1, 2, 4, 8, 16, 32, 64]
    assert bucket.bucket_m(10 ** 9) == bucket.dim_cap  # saturates at cap


def test_batch_policy_validation():
    with pytest.raises(ValueError):
        BatchPolicy(mode="lifo")
    with pytest.raises(ValueError):
        BatchPolicy(min_fill=0.0)


# ---------------------------------------------------------------------------
# SLO accounting arithmetic
# ---------------------------------------------------------------------------

def test_slo_summary_hand_built():
    recs = [
        RequestRecord(rid=0, tenant="a", arrival_s=0.0, prompt_len=10,
                      gen_len=2, slo_s=1.0, ttft_s=0.1, done_s=0.5),   # met
        RequestRecord(rid=1, tenant="a", arrival_s=1.0, prompt_len=4,
                      gen_len=1, slo_s=0.2, ttft_s=0.1, done_s=1.5),   # miss
        RequestRecord(rid=2, tenant="b", arrival_s=0.0, prompt_len=6,
                      gen_len=4, slo_s=2.0, ttft_s=0.3, done_s=2.0),   # met
    ]
    s = slo_summary(recs, makespan_s=2.0)
    assert s["requests"] == 3 and s["met"] == 2 and s["missed"] == 1
    assert s["deadline_miss_rate"] == pytest.approx(1 / 3)
    assert s["good_tokens"] == 12 + 10 and s["total_tokens"] == 27
    assert s["goodput_tps"] == pytest.approx(22 / 2.0)
    assert s["throughput_tps"] == pytest.approx(27 / 2.0)


def test_slo_summary_empty():
    s = slo_summary([], makespan_s=0.0)
    assert s["requests"] == 0 and s["deadline_miss_rate"] == 0.0
    assert s["goodput_tps"] == 0.0


# ---------------------------------------------------------------------------
# virtual-clock replay against the stub planner
# ---------------------------------------------------------------------------

def _simulate(mode="bucket", planner=None, trace=None, **kw):
    trace = generate_trace(_traffic()) if trace is None else trace
    return simulate(trace, planner or StubPlanner(),
                    {"a": STUB_CFG, "b": STUB_CFG},
                    policy=BatchPolicy(mode=mode), **kw)


@pytest.mark.parametrize("mode", ["bucket", "fifo"])
def test_simulate_completes_every_request(mode):
    trace = generate_trace(_traffic())
    result = _simulate(mode=mode, trace=trace)
    assert len(result.records) == len(trace)
    for rec in result.records:
        assert math.isfinite(rec.ttft_s) and rec.ttft_s >= 0
        assert math.isfinite(rec.done_s)
        assert rec.latency_s >= rec.ttft_s > 0 or rec.gen_len == 0
    assert result.makespan_s >= max(r.done_s for r in result.records) - 1e-12
    assert result.batches > 0 and result.dispatches > 0


def test_simulate_deterministic_section():
    assert serving_section(_simulate()) == serving_section(_simulate())


def test_simulate_first_encounter_charges_once():
    """Cold shapes pay the virtual compile exactly once; a fully
    precompiled pool pays none and finishes strictly earlier."""
    trace = generate_trace(_traffic())
    cold = _simulate(trace=trace)
    assert cold.cold_shapes == cold.distinct_shapes > 0
    policy = BatchPolicy()                  # the bucket mode _simulate runs
    pool = []
    for m in bucket_pool(_traffic().max_rows(policy), policy):
        for phase in PHASES:
            pool += batch_workload(STUB_CFG, m, phase)
    warm = _simulate(trace=trace, precompiled=pool)
    assert warm.cold_shapes == 0
    assert warm.makespan_s < cold.makespan_s


def test_simulate_fallback_pays_penalty_and_counts():
    """Unplanned shapes land in the fallback tally and the resolve rate
    drops below 1 — raggedness must be visible, never silent."""
    trace = [_req(0, arrival=0.0, prompt=8, gen=1)]
    ok = _simulate(trace=trace, planner=StubPlanner())
    assert ok.resolve_rate == 1.0
    # every decode/prefill M this 1-request trace emits is unplanned
    bad = _simulate(trace=trace,
                    planner=StubPlanner(unplanned={1, 8}))
    assert bad.resolve_rate < 1.0
    assert sum(c["fallback"] for c in bad.per_phase.values()) > 0
    # the penalty multiplies the roofline floor on the virtual clock
    dear = _simulate(trace=trace, planner=StubPlanner(unplanned={1, 8}),
                     costs=ServingCosts(fallback_penalty=1e4))
    assert dear.makespan_s > bad.makespan_s


def test_simulate_dispatch_hook_fires_once_per_shape():
    seen = []
    result = _simulate(dispatch=lambda shape, phase: seen.append(shape))
    assert len(seen) == len(set(seen)) == result.distinct_shapes


def test_serving_section_schema():
    section = serving_section(_simulate())
    for key in ("policy", "requests", "met", "missed", "deadline_miss_rate",
                "good_tokens", "total_tokens", "goodput_tps",
                "throughput_tps", "p50_latency_s", "p99_latency_s",
                "p50_ttft_s", "p99_ttft_s", "makespan_s", "batches",
                "cold_shapes", "distinct_shapes", "mean_batch_utilization",
                "resolve_rate", "per_phase"):
        assert key in section, key
    json.dumps(section)                           # report-embeddable
    for phase in ("prefill", "decode"):
        sub = section["per_phase"][phase]
        assert {"hit", "bucketed", "analytic", "fallback", "dispatches",
                "hit_rate", "resolve_rate"} <= set(sub)
    assert section["goodput_tps"] <= section["throughput_tps"] + 1e-9
    assert section["p50_latency_s"] <= section["p99_latency_s"] + 1e-12


# ---------------------------------------------------------------------------
# hypothesis properties (function-scoped guard: the non-property tests in
# this module must still run without hypothesis installed)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    _HAS_HYPOTHESIS = True
except ImportError:
    _HAS_HYPOTHESIS = False


if _HAS_HYPOTHESIS:
    _prompts = st.lists(st.integers(min_value=1, max_value=300),
                        min_size=1, max_size=24)

    @given(prompts=_prompts, max_batch=st.integers(1, 12))
    @settings(max_examples=80, deadline=None)
    def test_bucket_admission_stays_in_pool(prompts, max_batch):
        """Every bucket-mode batch's M is the padded pow-2 of its rows and
        a member of the warmed pool — admission never emits a GEMM the
        harness didn't pre-tune."""
        policy = BatchPolicy(mode="bucket", max_batch=max_batch)
        pool = set(bucket_pool(max_batch * max(prompts), policy))
        batcher = ContinuousBatcher(policy)
        for i, p in enumerate(prompts):
            batcher.submit(_req(i, arrival=0.0, prompt=p))
        for batch in _drain(batcher):
            assert batch.m == policy.bucket_m(batch.rows)
            assert batch.m in pool, (batch.m, sorted(pool))
            assert 0 < batch.utilization <= 1.0
            assert len(batch.requests) <= max_batch

    @given(plan=st.lists(st.tuples(st.booleans(),
                                   st.integers(0, 2),     # tenant index
                                   st.integers(1, 64)),   # prompt len
                         min_size=1, max_size=60),
           mode=st.sampled_from(["bucket", "fifo"]))
    @settings(max_examples=80, deadline=None)
    def test_conservation_under_interleavings(plan, mode):
        """Arbitrary submit/drain interleavings: at drain-out, every
        submitted rid was admitted exactly once, in FIFO order per
        tenant."""
        batcher = ContinuousBatcher(BatchPolicy(mode=mode))
        admitted, rid, now = [], 0, 0.0
        for drain_now, tenant, prompt in plan:
            now += 0.001
            batcher.submit(_req(rid, tenant=f"t{tenant}", arrival=now,
                                prompt=prompt))
            rid += 1
            if drain_now:
                b = batcher.next_prefill(now)
                if b is not None:
                    admitted += [r.rid for r in b.requests]
        admitted += [r.rid for b in _drain(batcher) for r in b.requests]
        assert sorted(admitted) == list(range(rid))
        assert batcher.pending() == 0
        # FIFO within tenant: rids are assigned in arrival order, so each
        # tenant's admitted positions must be increasing
        position = {r: i for i, r in enumerate(admitted)}
        for t_idx in {t for _, t, _ in plan}:
            rids = [r for r, (_, t, _) in enumerate(plan) if t == t_idx]
            positions = [position[r] for r in rids]
            assert positions == sorted(positions)
else:
    def test_bucket_admission_stays_in_pool():
        pytest.importorskip("hypothesis")

    def test_conservation_under_interleavings():
        pytest.importorskip("hypothesis")


# ---------------------------------------------------------------------------
# the end-to-end proof: serve --traffic on a routed multidevice mesh
# ---------------------------------------------------------------------------

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

TRAFFIC_BODY = textwrap.dedent("""
    import json
    import subprocess
    import sys

    out = sys.argv[1]
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "gemma-2b",
         "--smoke", "--traffic", "--traffic-requests", "6",
         "--traffic-tenants", "2", "--traffic-seed", "11",
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
    s = r["serving"]
    assert s["policy"] == "bucket"
    assert s["requests"] == 12 and s["met"] + s["missed"] == 12
    assert s["goodput_tps"] > 0 and s["throughput_tps"] > 0
    assert s["cold_shapes"] == 0, s            # admission stayed on pool
    assert 0 < s["p50_latency_s"] <= s["p99_latency_s"]
    for phase in ("prefill", "decode"):
        sub = s["per_phase"][phase]
        assert sub["dispatches"] > 0, s["per_phase"]
        assert sub["resolve_rate"] == 1.0, sub
        assert sub["hit_rate"] == 1.0, sub     # warmed pool: pure hits
    assert r["traffic"]["batch_mode"] == "bucket"
    # every pmm dispatch the replay executed carries full provenance
    assert r["dispatches"], "no pmm spans recorded"
    for d in r["dispatches"]:
        assert d["provenance"] in ("hit", "bucketed", "analytic",
                                   "fallback"), d
        assert d["tag"].startswith("traffic."), d
    # the trace has one marker per completed request
    t = json.load(open(out + "/trace.json"))
    marks = [e for e in t["traceEvents"]
             if e.get("name") == "serve.request"]
    assert len(marks) == 12, len(marks)
    assert all("latency_s" in m["args"] for m in marks)
    # the serving line renders from the same dict the report persists
    assert "serving [bucket]:" in proc.stdout
    print("ALL_OK")
""")


@pytest.mark.slow
def test_serve_traffic_multidevice(tmp_path):
    """Replayed mixed prefill/decode load on a routed multidevice gemma-2b
    serve: complete run report with serving section, 100% plan resolution,
    zero silent degrades."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    proc = subprocess.run(
        [sys.executable, "-c", TRAFFIC_BODY, str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, (f"stdout:\n{proc.stdout}\n"
                                  f"stderr:\n{proc.stderr}")
    assert "ALL_OK" in proc.stdout
