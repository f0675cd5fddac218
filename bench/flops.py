"""The work a dense decoder's serve step needs, from the published widths,
and the roofline arithmetic every family shares (`gemm_flops`,
`gemm_bytes`, `roofline_s`, `gemm_roofline_s`).

The dense counts are the `dense_decoder` family's (bench/families/); the
harness, the metrics and `bench/scopes.py` reach them only through a
family. They count what the algorithm requires, not what the program
executes, so a change that removes waste reads as a higher share of the
roofline:

- attention is causal over the real context, not over the preallocated
  cache length;
- the LM head counts only for the positions whose logits are used (the
  last position of the last prefill chunk, and every decode step);
- bytes are the weights read once per step (the embedding table only for
  the rows gathered, unless the tied LM head reads it whole), the KV cache
  of the context before the step read once, the step's new K/V written
  once, and the logits that are used written once in float32.

Every operand is bf16 (2 bytes), as served.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

BF16 = 2
F32 = 4


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int

    @classmethod
    def from_config(cls, cfg: dict) -> "Dims":
        """From a configuration file's published (Hugging Face) keys."""
        d, h = cfg["hidden_size"], cfg["num_attention_heads"]
        return cls(d=d, layers=cfg["num_hidden_layers"], heads=h,
                   kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg.get("head_dim") or d // h,
                   ffn=cfg["intermediate_size"], vocab=cfg["vocab_size"])

    def layer_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return self.d * (2 * q + 2 * kv) + 3 * self.d * self.ffn

    def kv_bytes_per_position(self) -> int:
        """K and V of one position over every layer, per sequence."""
        return 2 * self.layers * self.kv_heads * self.head_dim * BF16


def layer_gemms(dims: Dims, m: int) -> List[Tuple[str, int, int, int]]:
    """(tag, M, N, K) of one decoder layer's projections at M rows."""
    q = dims.heads * dims.head_dim
    kv = dims.kv_heads * dims.head_dim
    return [("attn.q", m, q, dims.d), ("attn.k", m, kv, dims.d),
            ("attn.v", m, kv, dims.d), ("attn.o", m, dims.d, q),
            ("mlp.gate", m, dims.ffn, dims.d), ("mlp.up", m, dims.ffn, dims.d),
            ("mlp.down", m, dims.d, dims.ffn)]


def step_gemms(dims: Dims, batch: int, q_len: int,
               logits_rows: int) -> List[Tuple[str, int, int, int]]:
    """Every GEMM one step needs, layers repeated: `q_len` new positions
    per sequence, logits used for `logits_rows` rows in all."""
    out = layer_gemms(dims, batch * q_len) * dims.layers
    if logits_rows:
        out.append(("lm_head", logits_rows, dims.vocab, dims.d))
    return out


def gemm_flops(m: int, n: int, k: int) -> float:
    return 2.0 * m * n * k


def gemm_bytes(m: int, n: int, k: int) -> float:
    return float(BF16 * (m * k + k * n + m * n))


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: compute- or bandwidth-bound."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def gemm_roofline_s(gemms, peak: dict) -> float:
    """Sum over GEMMs, each its own kernel, of its roofline time."""
    return sum(roofline_s(gemm_flops(m, n, k), gemm_bytes(m, n, k), peak)
               for _, m, n, k in gemms)


def attention_flops(dims: Dims, batch: int, q_start: int, q_len: int) -> float:
    """QK^T and PV of `q_len` queries at positions q_start.. , each causal
    over the keys up to and including its own position."""
    keys = q_len * q_start + q_len * (q_len + 1) // 2
    return 4.0 * batch * keys * dims.heads * dims.head_dim * dims.layers


def attention_work(dims: Dims, step: dict) -> Tuple[float, float]:
    """(needed FLOPs, needed bytes) of one step's attention over every
    layer: causal over the real context; the step's Q and output, and the
    K/V of the context up to its last new position read once, bf16."""
    b, q0, q = step["batch"], step["q_start"], step["q_len"]
    q_and_out = 2 * b * q * dims.heads * dims.head_dim * dims.layers
    nbytes = q_and_out * BF16 + b * (q0 + q) * dims.kv_bytes_per_position()
    return attention_flops(dims, b, q0, q), float(nbytes)


def step_flops(dims: Dims, batch: int, q_start: int, q_len: int,
               logits_rows: int) -> float:
    gemm = sum(gemm_flops(m, n, k)
               for _, m, n, k in step_gemms(dims, batch, q_len, logits_rows))
    return gemm + attention_flops(dims, batch, q_start, q_len)


def step_bytes(dims: Dims, batch: int, q_start: int, q_len: int,
               logits_rows: int) -> float:
    weights = dims.layers * dims.layer_params() * BF16
    if logits_rows:
        weights += dims.vocab * dims.d * BF16        # the tied LM head
    else:
        weights += batch * q_len * dims.d * BF16     # embedding rows only
    kv = batch * (q_start + q_len) * dims.kv_bytes_per_position()
    return float(weights + kv + logits_rows * dims.vocab * F32)


def step_work(dims: Dims, step: dict) -> Tuple[float, float]:
    """(needed FLOPs, needed bytes) of one logged serve step: a dict with
    `batch`, `q_start`, `q_len` and `logits_rows`."""
    args = (dims, step["batch"], step["q_start"], step["q_len"],
            step["logits_rows"])
    return step_flops(*args), step_bytes(*args)
