"""What a compiled serve step must say about where its work happens: the
scope vocabulary of docs/observability.md, read from the `op_name`
metadata of the compiled HLO, and which of its buffers hold a whole
layer's K/V or a whole cache stack. Shared by the CPU check
(`test_model_scopes.py`) and the described-v5e compile
(`test_tpu_compile.py`)."""
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import flops  # noqa: E402
from repro.models.model import decode_init, init_params  # noqa: E402
from repro.train.steps import make_serve_step  # noqa: E402

TOP_LEVEL = {"embed", "block", "final_norm", "lm_head"}
GEMM_TAGS = {tag for tag, *_ in flops.layer_gemms(
    flops.Dims(*[1] * 7), 1)} | {"lm_head"}
_OP = re.compile(r'^\s*(?:ROOT\s+)?%[\w.\-]+ = .*? ([\w\-]+)\(.*?'
                 r'metadata=\{op_name="([^"]*)"', re.M)


def compile_donated_steps(cfg, batch: int, chunk: int, max_len: int,
                          sharding=None) -> dict:
    """The serve step as the benchmark compiles it (the next token's argmax
    on the device, the cache donated) for a prefill chunk and a decode
    step, under the caller's gemm context: {kind: compiled}."""
    def shapes(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)

    params = shapes(jax.eval_shape(lambda k: init_params(k, cfg),
                                   jax.random.PRNGKey(0)))
    caches = shapes(jax.eval_shape(
        lambda: decode_init(None, cfg, batch, max_len)))
    serve = make_serve_step(cfg)

    def step(params, caches, tokens, positions):
        logits, caches = serve(params, caches, tokens, positions)
        return jnp.argmax(logits, -1).astype(jnp.int32)[:, None], caches

    step = jax.jit(step, donate_argnums=(1,))
    return {kind: step.lower(params, caches,
                             jax.ShapeDtypeStruct((batch, n), jnp.int32,
                                                  sharding=sharding),
                             jax.ShapeDtypeStruct((n,), jnp.int32,
                                                  sharding=sharding)
                             ).compile()
            for kind, n in (("prefill", chunk), ("decode", 1))}


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


_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([\w.\-]+) = (.+?) ([\w\-]+)\((.*)$')
_COMP = re.compile(r'^(?:ENTRY\s+)?%([\w.\-]+) .*\{$')
_ARRAY = re.compile(r'\b[a-z]+\d*\[([\d,]*)\]')
# ops that pass a buffer on or view it, and materialise nothing
_NO_BUFFER = {"parameter", "get-tuple-element", "tuple", "while", "bitcast",
              "conditional", "call"}


def _buffers(hlo_text: str):
    """(name, opcode, dims of each array in the result, operand names,
    op_name) of every instruction outside fusion and reducer bodies: the
    instructions whose results are buffers."""
    inner = set(re.findall(r'(?:calls|to_apply)=%([\w.\-]+)', hlo_text))
    out, comp = [], None
    for line in hlo_text.splitlines():
        head = _COMP.match(line)
        if head:
            comp = head.group(1)
            continue
        m = _INSTR.match(line)
        if not m or comp in inner:
            continue
        name, shape, opcode, rest = m.groups()
        dims = [tuple(int(d) for d in a.split(",") if d)
                for a in _ARRAY.findall(shape)]
        op_name = re.search(r'op_name="([^"]*)"', rest)
        out.append((name, opcode, dims,
                    re.findall(r'%([\w.\-]+)', rest.split("), ")[0]),
                    op_name.group(1) if op_name else ""))
    return out


def kv_copies(hlo_text: str, layer: tuple, n_layers: int,
              dot_operands: bool = False):
    """The instructions of a compiled serve step that copy, slice out or
    relayout a whole layer's K/V (`layer` = (B, Hkv, S, D)) or a whole
    stack of `n_layers`, as (opcode, dims, op_name): every buffer of that
    size, in any axis order, except the in-place write under
    `kv_cache.write` and ops that only pass a buffer on. With
    `dot_operands`, a layer read whose every user is a `dot` is allowed
    too: a backend whose dot cannot read a slice where it lies (the CPU's)
    materialises its operand, where the TPU fuses the read into the
    product."""
    def key(d):
        return sorted(x for x in d if x != 1)
    want_layer, want_stack = key(layer), key((n_layers, *layer))
    instrs = _buffers(hlo_text)
    users = {}
    for name, opcode, _, operands, _ in instrs:
        for o in operands:
            users.setdefault(o, []).append(opcode)
    bad = []
    for name, opcode, dims, _, op_name in instrs:
        kinds = {("stack" if key(d) == want_stack else "layer")
                 for d in dims if key(d) in (want_layer, want_stack)}
        if not kinds or opcode in _NO_BUFFER:
            continue
        if kinds == {"stack"} and "/kv_cache.write/" in op_name \
                and "dynamic-update-slice" in opcode + name:
            continue
        if dot_operands and kinds == {"layer"} and users.get(name) \
                and set(users[name]) == {"dot"}:
            continue
        bad.append((opcode, dims, op_name))
    return bad



# XLA's asynchronous moves of a buffer to another memory space (VMEM) and
# back: no relayout; a stack moved before the layer loop is still read once
_MOVES = ("copy-start", "copy-done", "slice-start", "slice-done")


def weight_copies(hlo_text: str, shapes, moves: bool = False):
    """The instructions of a compiled serve step that hold a buffer of a
    layer GEMM weight's or a weight stack's shape (`shapes`, dims in
    order; unit dims aside), as (opcode, dims, op_name): copies, slices or
    relayouts of a weight, in any memory space. The parameters themselves,
    and ops that only pass a buffer on or view it (a bitcast), hold none.
    With `moves`, XLA's asynchronous moves of a buffer between memory
    spaces (`_MOVES`, and the `ConcatBitcast` that joins the pieces of a
    sliced move) are let through."""
    def key(d):
        return [x for x in d if x != 1]
    want = [key(s) for s in shapes]
    joins = set(re.findall(r'%([\w.\-]+) = [^\n]*custom_call_target='
                           r'"ConcatBitcast"', hlo_text)) if moves else set()
    return [(opcode, dims, op_name)
            for name, opcode, dims, _, op_name in _buffers(hlo_text)
            if opcode not in _NO_BUFFER
            and not (moves and (opcode in _MOVES or name in joins))
            and any(key(d) in want for d in dims)]
