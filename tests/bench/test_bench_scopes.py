"""Device time by the program's scopes (`bench/scopes.py`) and the readings
built on it, against hand-worked values; the benchmark's own reduction of
the same traces, unchanged."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import flops  # noqa: E402
from bench import harness  # noqa: E402
from bench import reduce as red  # noqa: E402
from bench import scopes  # noqa: E402

RECORDED = ROOT / "bench" / "testdata" / "trace_olmo-1b.prefill-2k.json"
QK = "bqhgd,bkhd->bhgqk/dot_general"


def synthetic():
    """Device clock 1000 ns behind the host; window [0, 100). A prefill
    launched at 5 runs [10, 60): the embedding gather [10, 12), a `while`
    [12, 40) holding a q projection [12, 20), a cache write [20, 23), the
    QK einsum [23, 29), the scan's stacking [29, 33) and a squeeze with no
    op_name [33, 40); then the transposed embedding [40, 45), named after
    the argument, the LM head [45, 55) and the harness's argmax [55, 60).
    A decode launched at 70 runs [70, 90): the LM head [70, 76) and the
    final norm [76, 80)."""
    off = -1000
    mods = [["jit_bench_serve_step(1)", 10 + off, 50],
            ["jit_bench_serve_step(2)", 70 + off, 20]]
    ops = [["%gather.1 = bf16[...] gather(...)", 10, 2],
           ["%while.2 = (...) while(...)", 12, 28],
           ["%mmad.3 = f32[...] custom-call(...)", 12, 8],
           ["%dus.4 = bf16[...] dynamic-update-slice(...)", 20, 3],
           ["%conv.5 = f32[...] convolution(...)", 23, 6],
           ["%dus.6 = bf16[...] dynamic-update-slice(...)", 29, 4],
           ["%squeeze.7 = bf16[...] bitcast(...)", 33, 7],
           ["%transpose.8 = bf16[...] transpose(...)", 40, 5],
           ["%mmad.9 = f32[...] custom-call(...)", 45, 10],
           ["%reduce.10 = s32[...] reduce(...)", 55, 5],
           ["%mmad.9 = f32[...] custom-call(...)", 70, 6],
           ["%fusion.11 = f32[...] fusion(...)", 76, 4]]
    ops = [[n, s + off, d] for n, s, d in ops]
    host = [["bench.window", 0, 100], ["bench.prefill", 2, 8],
            [red.LAUNCH, 5, 1], ["bench.token_to_host", 60, 6],
            ["bench.decode_step", 66, 3], [red.LAUNCH, 70, 1],
            ["bench.token_to_host", 80, 20]]
    head = "jit(s)/lm_head/pmm.lm_head/dit_gemm.summa/pallas_call"
    body = "jit(s)/while/body/closed_call/block/"
    prefill = {"%gather.1": "jit(s)/embed/jit(_take)/gather",
               "%while.2": "jit(s)/while",
               "%mmad.3": body + "pmm.attn.q/dit_gemm.summa/pallas_call",
               "%dus.4": body + "kv_cache.write/dynamic_update_slice",
               "%conv.5": body + "pattn.attn.decode/" + QK,
               "%dus.6": "jit(s)/while/body/dynamic_update_slice",
               "%transpose.8": "params['embed']",
               "%mmad.9": head, "%reduce.10": "jit(s)/reduce"}
    decode = {"%mmad.9": head, "%fusion.11": "jit(s)/final_norm/norm/rsqrt"}
    return ({"chips": [{"modules": mods, "ops": ops}], "host": host},
            {"prefill": prefill, "decode": decode})


def ns(x):
    return pytest.approx(x * 1e-9)


def test_scope_seconds_of_a_synthetic_trace_by_hand():
    events, hlo = synthetic()
    by_op = scopes.op_seconds(events, ["prefill", "decode"], hlo)
    got = red.reduce(events, ["prefill", "decode"], hlo)["scope_s"]
    # attention: the QK einsum; LM head: 10 + 6 (its transposed weight is
    # named after the argument, so it is unscoped); unscoped: the scan's
    # stacking 4, the squeeze 7, the transpose 5, the argmax 5
    assert got == {"attention": ns(6), "lm_head": ns(16), "unscoped": ns(21)}
    parts = {}
    for (kind, op_name, _), secs in by_op.items():
        key = f"{kind}:{scopes.part(op_name)}"
        parts[key] = parts.get(key, 0.0) + secs
    assert parts == {"prefill:embed": ns(2),
                     "prefill:block/pmm.attn.q": ns(8),
                     "prefill:block/kv_cache.write": ns(3),
                     "prefill:block/pattn.attn.decode": ns(6),
                     "prefill:unscoped": ns(21),
                     "prefill:lm_head/pmm.lm_head": ns(10),
                     "decode:lm_head/pmm.lm_head": ns(6),
                     "decode:final_norm/norm": ns(4)}


def test_reduce_keeps_its_numbers_on_scoped_op_names():
    """The benchmark's reduction of the same trace: every key as it was
    worked out before the program named its scopes, with the scope path in
    the labels."""
    events, hlo = synthetic()
    r = red.reduce(events, ["prefill", "decode"], hlo)
    assert r["window_s"] == ns(100) and r["busy_s"] == ns(60)
    assert r["gemm_s"] == ns(24)                     # 8 + 10 + 6
    assert r["module_s"] == {"prefill": ns(50), "decode": ns(20)}
    assert r["steps_matched"] and r["modules"] == 2 and r["chips"] == 1
    assert dict(r["idle_gaps"]) == {"bench.prefill": ns(10),
                                    "bench.token_to_host": ns(30)}
    ops = dict(r["device_ops"])
    assert ops["lm_head/pmm.lm_head/dit_gemm.summa/pallas_call"] == ns(16)
    assert ops["block/pattn.attn.decode/" + QK] == ns(6)
    assert ops["dynamic_update_slice"] == ns(4)
    assert ops["block/kv_cache.write/dynamic_update_slice"] == ns(3)
    assert ops["(no scope) squeeze"] == ns(7)


def test_reduce_gives_the_scripts_scope_seconds_averaged_over_chips():
    """`scope_s`, as every traced benchmark run reduces it: the script's
    numbers on one chip, and their mean beside a chip that ran nothing."""
    events, hlo = synthetic()
    r = red.reduce(events, ["prefill", "decode"], hlo)
    assert r["scope_s"] == {"attention": ns(6), "lm_head": ns(16),
                            "unscoped": ns(21)}
    idle = {"modules": events["chips"][0]["modules"], "ops": []}
    two = dict(events, chips=events["chips"] + [idle])
    r = red.reduce(two, ["prefill", "decode"], hlo)
    assert r["chips"] == 2 and r["busy_s"] == ns(30)
    assert r["scope_s"] == {"attention": ns(3), "lm_head": ns(8),
                            "unscoped": ns(10.5)}


@pytest.mark.parametrize("op_name, want", [
    (None, "unscoped"),
    ("params['embed']", "unscoped"),
    ("jit(s)/while/body/dynamic_slice", "unscoped"),
    ("jit(s)/embed/gather", "embed"),
    ("jit(s)/while/body/block/add", "block"),
    ("jit(s)/while/body/block/rope/mul", "block/rope"),
    ("jit(s)/while/body/block/pattn.attn.decode/exp", "block/pattn.attn.decode"),
    ("jit(s)/lm_head/transpose", "lm_head"),
    ("jit(s)/lm_head/pmm.lm_head/dit_gemm.summa/pad", "lm_head/pmm.lm_head"),
])
def test_part_names_the_top_scope_and_the_finest_inside(op_name, want):
    assert scopes.part(op_name) == want


def test_pmm_lm_head_alone_is_not_the_lm_head():
    events, hlo = synthetic()
    hlo["decode"]["%mmad.9"] = "jit(s)/pmm.lm_head/dot_general"
    got = red.reduce(events, ["prefill", "decode"], hlo)["scope_s"]
    # the decode step's head (6) leaves `lm_head` for `unscoped`
    assert got == {"attention": ns(6), "lm_head": ns(10), "unscoped": ns(27)}


# -- the readers, by hand ---------------------------------------------------
# dims d 8, 2 layers, 2 heads of 4, 1 KV head, vocab 32; peaks 1e3 FLOP/s
# and 1e2 B/s. KV per position: 2 * 2 layers * 1 * 4 * 2 B = 32 B.
# Prefill (B 2, positions 0-1, no logits): 3 causal keys per row, FLOPs
# 4*2*3*2*4*2 = 384; bytes Q+out 2*2*2*2*4*2 = 128 values = 256 B, K/V
# 2*2*32 = 128 B: 3.84 s. Decode (B 2, position 2): 3 keys, 384 FLOPs;
# 64 values = 128 B + 2*3*32 = 192 B: 3.2 s. LM head (2, 32, 8): 1024 FLOPs,
# 2*(16 + 256 + 64) = 672 B: 6.72 s.

DIMS = flops.Dims(d=8, layers=2, heads=2, kv_heads=1, head_dim=4, ffn=16,
                  vocab=32)
PEAK = {"bf16_flops": 1e3, "hbm_bytes_per_s": 1e2}
STEPS = [{"kind": "prefill", "batch": 2, "q_start": 0, "q_len": 2,
          "logits_rows": 0},
         {"kind": "decode", "batch": 2, "q_start": 2, "q_len": 1,
          "logits_rows": 2}]


FAMILY = harness.load_family("dense_decoder")


def run(**scope_s):
    s = {"attention": 14.08, "lm_head": 26.88, "unscoped": 3.0}
    s.update(scope_s)
    return {"family": FAMILY, "dims": DIMS, "peak": PEAK, "steps": STEPS,
            "trace": {"busy_s": 12.0, "window_s": 13.0, "scope_s": s}}


def test_attention_work_by_hand():
    assert FAMILY.attention_work(DIMS, STEPS[0]) == (384.0, 384.0)
    assert FAMILY.attention_work(DIMS, STEPS[1]) == (384.0, 320.0)


def test_readers_by_hand():
    r = run()
    assert scopes.attention_roofline(r) == pytest.approx(50.0)  # 7.04/14.08
    assert scopes.lm_head_roofline(r) == pytest.approx(25.0)    # 6.72/26.88
    assert scopes.unscoped_share(r) == pytest.approx(25.0)      # 3/12


def test_readers_read_nothing_without_their_scope():
    for reader in scopes.READERS.values():
        assert reader(dict(run(), trace=None)) is None
        # a trace reduced without scope_s (a run with no device plane)
        assert reader(dict(run(), trace={"busy_s": 12.0})) is None
    assert scopes.attention_roofline(run(attention=0.0)) is None
    assert scopes.lm_head_roofline(run(lm_head=0.0)) is None
    # a program that names none of its layers: not 100% unscoped
    assert scopes.unscoped_share(run(lm_head=0.0, unscoped=12.0)) is None
    assert scopes.lm_head_roofline(dict(run(), steps=STEPS[:1])) is None
    assert scopes.attention_roofline(dict(run(), steps=[])) is None


# -- the trace recorded on the chip -----------------------------------------

@pytest.fixture(scope="module")
def recorded():
    return harness.load_json(RECORDED)


def test_recorded_trace_reduces_as_before(recorded):
    r = red.reduce(recorded, recorded["step_kinds"], recorded["scopes"])
    assert r["window_s"] == pytest.approx(0.323925534, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.322609439, rel=1e-9)
    assert r["gemm_s"] == pytest.approx(0.149794616, rel=1e-9)
    assert r["module_s"] == {"prefill": pytest.approx(0.292684256, rel=1e-9),
                             "decode": pytest.approx(0.029927296, rel=1e-9)}
    assert dict(r["idle_gaps"]) == {
        "bench.prefill": pytest.approx(0.001302222, rel=1e-9),
        "bench.token_to_host": pytest.approx(1.3873e-05, rel=1e-9)}


def test_recorded_trace_predates_the_scopes(recorded):
    """The recorded program named only `dit_gemm.*`: every op is unscoped,
    the GEMM time matches the reduction's, and the readers read nothing."""
    kinds, hlo = recorded["step_kinds"], recorded["scopes"]
    by_op = scopes.op_seconds(recorded, kinds, hlo)
    r = red.reduce(recorded, kinds, hlo)
    total = sum(by_op.values())
    assert 0 < total <= r["busy_s"]
    assert r["scope_s"] == {"attention": 0.0, "lm_head": 0.0,
                            "unscoped": pytest.approx(total, rel=1e-9)}
    assert sum(v for (_, n, _), v in by_op.items()
               if n and red.GEMM_SCOPE in n) == pytest.approx(r["gemm_s"])
    steps = [{"kind": k, "batch": 4, "q_start": 0, "q_len": 1,
              "logits_rows": 4} for k in kinds]
    out = scopes.summary(by_op, r, {"steps": steps, "peak": PEAK,
                                    "family": FAMILY, "dims": DIMS})
    assert out["scope_s"] == r["scope_s"]
    assert out["readings"] == dict.fromkeys(scopes.READERS)
    assert out["parts"]["prefill:unscoped"] > out["parts"]["decode:unscoped"]


def test_traced_window_runs_at_smoke_width():
    """The script's path on the CPU: the window runs and is traced, but a
    CPU trace has no TPU plane, so nothing is read."""
    from test_bench_harness import SMOKE_CONFIG, SMOKE_TRAFFIC
    cell = harness.Cell(name="smoke", config=dict(SMOKE_CONFIG),
                        family=FAMILY, traffic=dict(SMOKE_TRAFFIC), limits={},
                        end_to_end=[], per_layer=[])
    out = scopes.traced_window(cell, 2 ** 33 + 5, 1.0, PEAK)
    assert out["routed_gemms"] == {"prefill": (7, 1), "decode": (7, 1)}
    assert out["steps"]["prefill"] > 0 and out["steps"]["decode"] > 0
    assert out["readings"] == dict.fromkeys(scopes.READERS)
