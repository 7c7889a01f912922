"""Operations and bytes that the algorithm needs, from shapes alone.

For the dense GQA decoder that both configurations share: pre-norm
attention (H query heads, KV key/value heads of size hd) and a gated MLP
of width ff, L layers, a vocabulary head of V x d. A multiply-add counts
as two operations. Weights and the K/V cache are bf16 (2 bytes).

What the program does beyond this (the padded cache it reads to
``max_seq``, the empty slots it decodes, the prompt positions whose logits
it drops) is not counted: a program that stops doing it comes nearer the
roofline, and the yardstick stays where it is.
"""
from __future__ import annotations

from typing import Dict, Iterable

BYTES = 2  # bf16 weights and cache


def layer_matmul_params(m: Dict) -> int:
    d, h, kv, hd, ff = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                        m["head_dim"], m["d_ff"])
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff


def head_params(m: Dict) -> int:
    return m["vocab_size"] * m["d_model"]


def matmul_params(m: Dict) -> int:
    """Parameters that each token that yields logits multiplies by."""
    return m["num_layers"] * layer_matmul_params(m) + head_params(m)


def attn_flops(m: Dict, context: int) -> int:
    """Scores and weighted values of one query over ``context`` keys, in
    every layer."""
    return 4 * m["num_layers"] * m["num_heads"] * m["head_dim"] * context


def kv_bytes_per_token(m: Dict) -> int:
    return m["num_layers"] * 2 * m["num_kv_heads"] * m["head_dim"] * BYTES


def weight_bytes(m: Dict) -> int:
    """Every weight a decode step reads once: the layers' matrices and
    norms, the final norm and the head (the embedding rows are counted per
    token)."""
    d = m["d_model"]
    return (m["num_layers"] * (layer_matmul_params(m) + 2 * d) + d
            + head_params(m)) * BYTES


def decode_call(m: Dict, positions: Iterable[int]) -> Dict[str, float]:
    """One decode step over the active slots, each writing its new token
    at ``pos`` and attending over positions 0..pos: the cache is read up
    to each slot's own position, not to the padded length."""
    pos = list(positions)
    d = m["d_model"]
    flops = sum(2 * matmul_params(m) + attn_flops(m, p + 1) for p in pos)
    kv = kv_bytes_per_token(m)
    nbytes = (weight_bytes(m) + sum(p * kv + kv for p in pos)
              + len(pos) * d * BYTES)
    return {"flops": float(flops), "bytes": float(nbytes)}


def prefill_flops(m: Dict, n: int) -> float:
    """A prompt of n tokens: every layer at every position, causal
    attention over the positions before it, and the head once, for the
    last position."""
    layers = m["num_layers"] * layer_matmul_params(m)
    attn = 4 * m["num_layers"] * m["num_heads"] * m["head_dim"] \
        * n * (n + 1) // 2
    return float(2 * layers * n + attn + 2 * head_params(m))


def roofline_seconds(flops: float, nbytes: float, peak: Dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
