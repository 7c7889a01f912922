"""Weights drawn from the seed, on the device, in one jitted call.

The benchmark makes the weights, in the dtype they are served in (bf16),
and hands the same arrays to the program under test and to the plain
reference. Layout (the benchmark's own; ``program_params`` rearranges it
into the program's tree without copying):

  embed (V, d)          std 1; 1/sqrt(d) when tied (it is the head too)
  head (V, d)           std 1/sqrt(d); absent when the embedding is tied
  final_norm (d,)       ones
  layers: ln1, ln2 (L, d) ones; wq (L, d, H, hd); wk, wv (L, d, KV, hd);
          wo (L, H, hd, d); w_gate, w_in (L, d, ff); w_out (L, ff, d),
          each of std 1/sqrt(fan_in)

With these scales the final hidden state has unit RMS and a logit has a
standard deviation near 1, so greedy tokens are not all near-ties.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (beyond 32 bits too)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def shapes(m: Dict) -> Dict:
    L, d, h, kv, hd, ff, v = (m["num_layers"], m["d_model"], m["num_heads"],
                              m["num_kv_heads"], m["head_dim"], m["d_ff"],
                              m["vocab_size"])
    layers = {"ln1": (L, d), "ln2": (L, d), "wq": (L, d, h, hd),
              "wk": (L, d, kv, hd), "wv": (L, d, kv, hd),
              "wo": (L, h, hd, d), "w_gate": (L, d, ff),
              "w_in": (L, d, ff), "w_out": (L, ff, d)}
    out = {"embed": (v, d), "final_norm": (d,), "layers": layers}
    if not m["tie_embeddings"]:
        out["head"] = (v, d)
    return out


def _std(name: str, shape, tied: bool) -> float:
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


def make_weights(m: Dict, seed: int) -> Dict:
    sh = shapes(m)
    dtype = jnp.dtype(m["param_dtype"])

    def make(key):
        flat = [("embed", sh["embed"]), ("final_norm", sh["final_norm"])]
        if "head" in sh:
            flat.append(("head", sh["head"]))
        flat += [(k, s) for k, s in sh["layers"].items()]
        keys = jax.random.split(key, len(flat))
        vals = {}
        for k, (name, shape) in zip(keys, flat):
            if name in ("final_norm", "ln1", "ln2"):
                vals[name] = jnp.ones(shape, dtype)
            else:
                vals[name] = (jax.random.normal(k, shape, jnp.float32)
                              * _std(name, shape, m["tie_embeddings"])
                              ).astype(dtype)
        out = {n: vals[n] for n in ("embed", "final_norm", "head")
               if n in vals}
        out["layers"] = {n: vals[n] for n in sh["layers"]}
        return out

    return jax.jit(make)(seed_key(seed))


def program_params(w: Dict) -> Dict:
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
