"""The reduction from a profiler trace to per-layer numbers, and the
FLOPs/bytes arithmetic, against hand-worked values.

`bench/testdata/trace_olmo-1b.prefill-2k.json` is the start of a traced
window recorded on one TPU v5e by `bench/run.py --trace 1`, cut down to
its first three programs and the host events and HLO scopes they need: a
(4, 2048) prefill chunk and two (4, 1) decode steps of the routed olmo-1b
serve step.
"""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import flops  # noqa: E402
from bench import reduce as red  # noqa: E402
from bench.harness import load_family, load_json, metric_reader  # noqa: E402

OLMO = load_json(ROOT / "bench" / "configs" / "olmo-1b.json")
FAMILY = load_family(OLMO["family"])
PEAK = load_json(ROOT / "bench" / "peaks.json")["TPU v5 lite"]
RECORDED = ROOT / "bench" / "testdata" / "trace_olmo-1b.prefill-2k.json"


# -- synthetic trace: every number worked by hand ---------------------------

def synthetic():
    """Device clock 1000 ns behind the host. Window [0, 100). Two programs:
    a prefill launched at 5 running [10, 40) on the host clock, holding a
    `while` [10, 30) that contains the GEMM [10, 20) and an add [22, 30),
    then a copy [32, 40); a decode launched at 50 running [50, 60) with one
    GEMM [50, 58). The decode starts as it is launched, which fixes the
    clock offset at 1000 ns."""
    off = -1000
    mods = [["jit_bench_serve_step(1)", 10 + off, 30],
            ["jit_bench_serve_step(2)", 50 + off, 10]]
    ops = [["%while.1 = (...) while(...)", 10 + off, 20],
           ["%mmad.1 = f32[...] custom-call(...)", 10 + off, 10],
           ["%add.2 = f32[...] add(...)", 22 + off, 8],
           ["%copy.3 = f32[...] copy(...)", 32 + off, 8],
           ["%mmad.1 = f32[...] custom-call(...)", 50 + off, 8]]
    host = [["bench.window", 0, 100], ["bench.prefill", 2, 8],
            [red.LAUNCH, 5, 1], ["bench.token_to_host", 40, 4],
            ["bench.decode_step", 44, 3], [red.LAUNCH, 50, 1],
            ["bench.token_to_host", 60, 35]]
    scopes = {"prefill": {"%while.1": "jit(s)/while", "%mmad.1":
                          "jit(s)/while/body/dit_gemm.summa/pallas_call",
                          "%add.2": "jit(s)/while/body/add"},
              "decode": {"%mmad.1": "jit(s)/dit_gemm.summa/pallas_call"}}
    return {"chips": [{"modules": mods, "ops": ops}], "host": host}, scopes


def test_synthetic_trace_reduces_to_hand_worked_numbers():
    events, scopes = synthetic()
    r = red.reduce(events, ["prefill", "decode"], scopes)
    assert r["window_s"] == pytest.approx(100e-9)
    # busy: [10, 30), [32, 40) and [50, 58)
    assert r["busy_s"] == pytest.approx(36e-9)
    assert r["gemm_s"] == pytest.approx(18e-9)          # 10 + 8, leaves only
    assert r["module_s"] == {"prefill": pytest.approx(30e-9),
                             "decode": pytest.approx(10e-9)}
    assert r["steps_matched"] and r["modules"] == 2
    # idle [0, 10) under bench.prefill (8 of 10 ns), [30, 32) under no
    # span, [40, 50) under token_to_host (4) rather than decode_step (3),
    # [58, 100) under token_to_host
    idle = dict(r["idle_gaps"])
    assert idle["bench.prefill"] == pytest.approx(10e-9)
    assert idle["(no harness span)"] == pytest.approx(2e-9)
    assert idle["bench.token_to_host"] == pytest.approx(52e-9)
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    ops = dict(r["device_ops"])
    assert ops["dit_gemm.summa/pallas_call"] == pytest.approx(18e-9)
    assert ops["add"] == pytest.approx(8e-9)
    assert ops["(no scope) copy"] == pytest.approx(8e-9)


def test_clock_offset_needs_one_launch_per_program():
    events, _ = synthetic()
    mods = events["chips"][0]["modules"]
    assert red.clock_offset(mods, events["host"]) == 1000
    assert red.clock_offset(mods, events["host"][:3]) == 0.0


def test_leaves_drop_containers():
    ops = [("w", 0, 10), ("a", 0, 4), ("b", 5, 5), ("c", 12, 1)]
    assert [e[0] for e in red.leaves(ops)] == ["a", "b", "c"]


def test_op_scopes_reads_hlo_metadata():
    hlo = ('  %mmad.60 = f32[8,8]{1,0} custom-call(%a, %b), '
           'custom_call_target="tpu_custom_call", metadata={op_name='
           '"jit(s)/dit_gemm.summa/pallas_call" stack_frame_id=3}\n'
           '  ROOT %t.1 = (f32[8]) tuple(%x), metadata={op_name="jit(s)/t"}\n'
           '  %p = f32[8] parameter(0)\n')
    assert red.op_scopes(hlo) == {"%mmad.60": "jit(s)/dit_gemm.summa/"
                                  "pallas_call", "%t.1": "jit(s)/t"}


# -- the trace recorded on the chip -----------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return load_json(RECORDED)


def test_recorded_trace_reduces(recorded):
    r = red.reduce(recorded, recorded["step_kinds"], recorded["scopes"])
    assert r["steps_matched"] and r["modules"] == 3
    assert r["module_s"]["prefill"] > 5 * r["module_s"]["decode"]
    assert 0 < r["gemm_s"] < r["busy_s"] <= r["window_s"]
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-9)
    assert r["device_ops"][0][0].startswith("dit_gemm.summa/")
    # aligned: every program starts after the host launched it
    host = recorded["host"]
    launches = [h for h in host if h[0] == red.LAUNCH]
    off = red.clock_offset(recorded["chips"][0]["modules"], host)
    for h, m in zip(launches, recorded["chips"][0]["modules"]):
        assert m[1] + off >= h[1]


def test_recorded_trace_metrics_stay_under_their_peaks(recorded):
    r = red.reduce(recorded, recorded["step_kinds"], recorded["scopes"])
    steps = [{"kind": "prefill", "batch": 4, "q_start": 0, "q_len": 2048,
              "logits_rows": 4},
             {"kind": "decode", "batch": 4, "q_start": 2048, "q_len": 1,
              "logits_rows": 4},
             {"kind": "decode", "batch": 4, "q_start": 2049, "q_len": 1,
              "logits_rows": 4}]
    run = {"trace": r, "steps": steps, "peak": PEAK, "family": FAMILY,
           "dims": FAMILY.dims(OLMO),
           "routed_gemms": {"prefill": (7, 1), "decode": (7, 1)}}
    values = {m: metric_reader(m)(run) for m in
              ("gemm_roofline", "step_mfu", "decode_hbm_share",
               "device_idle_share")}
    assert all(0 < v < 100 for v in values.values()), values


HLO = """\
%fusion.1 = bf16[8,128]{1,0} fusion(%p), kind=kLoop, metadata={op_name="jit(s)/jit(_take)/gather"}
%custom-call.7 = bf16[8,6144]{1,0} custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/while/body/closed_call/dit_gemm.summa/while/body/closed_call/jit(mmad)/pallas_call"}
%custom-call.8 = bf16[8,2048]{1,0} custom-call(%c, %d), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/while/body/closed_call/dit_gemm.summa/while/body/closed_call/jit(mmad)/pallas_call"}
%custom-call.9 = bf16[8,50304]{1,0} custom-call(%e, %f), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/dit_gemm.summa/while/body/closed_call/jit(mmad)/pallas_call"}
%custom-call.4 = bf16[8,2048]{1,0} custom-call(), custom_call_target="AllocateBuffer", metadata={op_name="jit(s)/dit_gemm.summa/x"}
%convolution.2 = f32[8,16,1,2048]{3,2,1,0} convolution(%q, %k), dim_labels=bf_oi->bf, metadata={op_name="jit(s)/while/body/closed_call/bqhd,bkhd->bhqk/dot_general"}
ROOT %dot.3 = f32[8,64]{1,0} dot(%g, %h), lhs_contracting_dims={1}, metadata={op_name="jit(s)/while/body/dit_gemm.auto/dot_general"}
"""


def test_routed_gemms_counts_products_under_dit_gemm_scopes():
    # two Pallas kernels and an XLA dot inside the layer loop, the head's
    # kernel outside; attention's einsum and the buffer are no GEMMs
    assert red.routed_gemms(HLO) == (3, 1)
    assert red.routed_gemms("") == (0, 0)


def test_gemm_roofline_reads_nothing_once_a_gemm_leaves_the_routed_path():
    steps = [{"kind": "decode", "batch": 4, "q_start": 16, "q_len": 1,
              "logits_rows": 4}]
    trace = {"gemm_s": 1e-3, "window_s": 1e-2, "busy_s": 1e-2}
    run = {"trace": trace, "steps": steps, "peak": PEAK, "family": FAMILY,
           "dims": FAMILY.dims(OLMO)}
    read = metric_reader("gemm_roofline")
    assert read(dict(run, routed_gemms={"decode": (7, 1)})) > 0
    # the LM head on a plain XLA dot, a projection off the path, no program
    for routed in ({"decode": (7, 0)}, {"decode": (6, 1)}, {}):
        assert read(dict(run, routed_gemms=routed)) is None


# -- FLOPs and bytes, olmo-1b by hand ---------------------------------------
# layer: 4*2048^2 (q, k, v, o) + 3*2048*8192 (gate, up, down) = 67,108,864
# decode B 4 at position 2048: GEMMs 2*4*67,108,864*16 + LM head
# 2*4*50,304*2048 = 9,414,115,328; attention 4*4*2049 keys*16*128*16
# = 1,074,266,112. Bytes: layer weights 2,147,483,648 + tied head
# 206,045,184 + KV 4*2049*131,072 = 1,074,266,112 + logits 4*50,304*4.

def test_olmo_layer_and_decode_step_by_hand():
    d = flops.Dims.from_config(OLMO)
    assert d.layer_params() == 67_108_864
    assert d.kv_bytes_per_position() == 131_072
    step = {"batch": 4, "q_start": 2048, "q_len": 1, "logits_rows": 4}
    f, b = flops.step_work(d, step)
    assert f == 9_414_115_328 + 1_074_266_112
    assert b == 2_147_483_648 + 206_045_184 + 1_074_266_112 + 804_864
    assert flops.roofline_s(f, b, PEAK) == pytest.approx(b / 819e9)


def test_olmo_prefill_chunk_by_hand():
    d = flops.Dims.from_config(OLMO)
    # GEMMs 2*8192*67,108,864*16 + LM head for the 4 last positions only;
    # causal attention: 2048*2049/2 keys per row
    f, _ = flops.step_work(d, {"batch": 4, "q_start": 0, "q_len": 2048,
                               "logits_rows": 4})
    assert f == 17_592_186_044_416 + 824_180_736 + 1_100_048_498_688
    # a chunk whose logits are not used reads the embedding rows only
    f0, b0 = flops.step_work(d, {"batch": 4, "q_start": 0, "q_len": 512,
                                 "logits_rows": 0})
    assert f0 == (2 * 2048 * 67_108_864 * 16
                  + 4 * 4 * (512 * 513 // 2) * 16 * 128 * 16)
    assert b0 == 2_147_483_648 + 2048 * 2048 * 2 + 4 * 512 * 131_072


def test_gemm_roofline_sums_each_gemm():
    d = flops.Dims.from_config(OLMO)
    gemms = flops.step_gemms(d, 4, 1, 4)
    assert len(gemms) == 7 * 16 + 1 and gemms[-1] == ("lm_head", 4, 50304,
                                                      2048)
    # at M = 4 every GEMM is bandwidth-bound: the bytes of each over 819e9
    want = sum(2 * (m * k + k * n + m * n) for _, m, n, k in gemms) / 819e9
    assert flops.gemm_roofline_s(gemms, PEAK) == pytest.approx(want)
