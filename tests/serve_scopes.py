"""What a compiled serve step must say about where its work happens: the
scope vocabulary of docs/observability.md, read from the `op_name`
metadata of the compiled HLO. Shared by the CPU check
(`test_model_scopes.py`) and the described-v5e compile
(`test_tpu_compile.py`)."""
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import flops  # noqa: E402

TOP_LEVEL = {"embed", "block", "final_norm", "lm_head"}
GEMM_TAGS = {tag for tag, *_ in flops.layer_gemms(
    flops.Dims(*[1] * 7), 1)} | {"lm_head"}
_OP = re.compile(r'^\s*(?:ROOT\s+)?%[\w.\-]+ = .*? ([\w\-]+)\(.*?'
                 r'metadata=\{op_name="([^"]*)"', re.M)


def instructions(hlo_text: str):
    """(opcode, op_name) of every instruction that carries an op_name."""
    return [(m.group(1), m.group(2)) for m in _OP.finditer(hlo_text)]


def assert_names_every_part(hlo_text: str) -> None:
    """Every op of a routed GEMM sits under one `pmm.<tag>` with one of
    the dense decoder's eight tags; every other matrix product (by opcode,
    or an einsum XLA rewrote, by its `dot_general` name) under `pattn.`;
    the cache writes under `kv_cache.write`; and no product outside the
    four top-level scopes."""
    ops = instructions(hlo_text)
    for _, op_name in ops:
        segs = op_name.split("/")
        if any(s.startswith("dit_gemm.") for s in segs):
            tags = [s[len("pmm."):] for s in segs if s.startswith("pmm.")]
            assert len(tags) == 1 and tags[0] in GEMM_TAGS, op_name
    products = [n for k, n in ops
                if k in ("dot", "convolution") or n.endswith("dot_general")]
    assert products, "no matrix product in the step"
    for op_name in products:
        segs = op_name.split("/")
        assert TOP_LEVEL & set(segs), f"product outside the model: {op_name}"
        if not any(s.startswith("dit_gemm.") for s in segs):
            assert any(s.startswith("pattn.") for s in segs), op_name
    assert any(s.startswith("pattn.") for n in products
               for s in n.split("/")), "no attention product"
    writes = [n for _, n in ops if "/kv_cache.write/" in n]
    assert any(n.endswith("dynamic_update_slice") for n in writes), \
        "no cache write under kv_cache.write"
    assert all("/block/" in n for n in writes), writes
