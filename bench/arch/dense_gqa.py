"""The dense GQA decoder that both configurations run: the benchmark's
weight layout, the program's parameter tree, and the operations and bytes
that a call needs, from shapes alone.

Llama-style, as InternLM2 and Granite-8B-Code are: pre-norm attention (H
query heads, KV key/value heads of size hd) and a gated MLP of width ff,
L layers, a vocabulary head of V x d (the embedding when tied).

Weight layout (``layout``, in the order ``bench/weights.py`` draws it):

  embed (V, d)          std 1; 1/sqrt(d) when tied (it is the head too)
  final_norm (d,)       ones
  head (V, d)           std 1/sqrt(d); absent when the embedding is tied
  layers: ln1, ln2 (L, d) ones; wq (L, d, H, hd); wk, wv (L, d, KV, hd);
          wo (L, H, hd, d); w_gate, w_in (L, d, ff); w_out (L, ff, d),
          each of std 1/sqrt(fan_in)

With these scales the final hidden state has unit RMS and a logit has a
standard deviation near 1, so greedy tokens are not all near-ties.

Counts: a multiply-add counts as two operations; weights and the K/V cache
are bf16 (2 bytes). What the program does beyond the algorithm (the padded
cache it reads to ``max_seq``, the empty slots it decodes, the prompt
positions whose logits it drops) is not counted: a program that stops
doing it comes nearer the roofline, and the yardstick stays where it is.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Tuple

BYTES = 2  # bf16 weights and cache

# the widths of the CPU smoke runs (tests/bench/smoke.py)
SMOKE = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 16, "d_ff": 128, "vocab_size": 256}


# -- weights -----------------------------------------------------------------

def _std(name: str, shape, tied: bool):
    """The standard deviation of a tensor's draw; None: ones."""
    if name in ("final_norm", "ln1", "ln2"):
        return None
    if name == "embed":
        # a tied embedding is also the head: 1/sqrt(d) keeps a logit's
        # standard deviation near 1 (std 1 would give sqrt(d), and every
        # greedy token would win by a wide margin)
        return shape[1] ** -0.5 if tied else 1.0
    if name == "head":
        return shape[1] ** -0.5
    if name == "wo":
        return (shape[1] * shape[2]) ** -0.5
    return shape[1] ** -0.5          # (L, fan_in, ...)


def layout(m: Dict) -> List[Tuple[tuple, tuple, object]]:
    """Each tensor as (path in the weight tree, shape, std or None for
    ones), in draw order."""
    L, d, h, kv, hd, ff, v = (m["num_layers"], m["d_model"], m["num_heads"],
                              m["num_kv_heads"], m["head_dim"], m["d_ff"],
                              m["vocab_size"])
    tied = m["tie_embeddings"]
    top = [("embed", (v, d)), ("final_norm", (d,))]
    if not tied:
        top.append(("head", (v, d)))
    layers = {"ln1": (L, d), "ln2": (L, d), "wq": (L, d, h, hd),
              "wk": (L, d, kv, hd), "wv": (L, d, kv, hd),
              "wo": (L, h, hd, d), "w_gate": (L, d, ff),
              "w_in": (L, d, ff), "w_out": (L, ff, d)}
    return [((n,), s, _std(n, s, tied)) for n, s in top] + \
        [(("layers", n), s, _std(n, s, tied)) for n, s in layers.items()]


def program_params(w: Dict, m: Dict) -> Dict:
    """The program's parameter tree (``repro.models.model.model_schema``
    for a dense decoder: one scanned segment), sharing w's arrays."""
    ly = w["layers"]
    embed = {"tokens": w["embed"]}
    if "head" in w:
        embed["head"] = w["head"]
    seg = {"ln1": {"scale": ly["ln1"]},
           "attn": {"wq": ly["wq"], "wk": ly["wk"], "wv": ly["wv"],
                    "wo": ly["wo"]},
           "ln2": {"scale": ly["ln2"]},
           "mlp": {"w_in": ly["w_in"], "w_gate": ly["w_gate"],
                   "w_out": ly["w_out"]}}
    return {"embed": embed, "final_norm": {"scale": w["final_norm"]},
            "segments": (seg,)}


# -- operations and bytes ----------------------------------------------------

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
