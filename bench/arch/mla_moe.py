"""DeepSeek-V2's block, as the program runs it: latent attention (MLA)
with a low-rank q and YaRN rope, then a dense MLP in the leading
``dense_layer_prefix`` layers and an expert layer in the rest, of which
this chip holds ``moe.num_experts`` routed experts (from
``moe.first_expert``) of the router's ``moe.router_experts``, plus the
shared experts; the benchmark's weight layout, the program's parameter
tree, and the operations and bytes that a call needs, from shapes alone.

The MLA widths are the program's (``repro.configs`` entry
``program_name``, whose ``MLAConfig`` a configuration file cannot set), so
``layout`` reads them from the program's config for the file's model;
that also refuses, before any weight is drawn, a model the program cannot
take.

Weight layout (``layout``, in draw order), every tensor stacked over its
segment's layers as the program stacks it:

  embed (V, d) std 1; final_norm (d,) ones; head (V, d) std 1/sqrt(d)
  dense / moe_layers, each of n layers:
    ln1, ln2 (n, d) ones; wq_a (n, d, qr); q_norm (n, qr) ones;
    wq_b (n, qr, H, nope + rope); w_dkv (n, d, r + rope);
    kv_norm (n, r) ones; w_uk (n, r, H, nope); w_uv (n, r, H, v);
    wo (n, H, v, d)
  dense:      w_gate, w_in (n, d, ff_dense); w_out (n, ff_dense, d)
  moe_layers: router (n, d, R); w_gate, w_in (n, E, d, ff);
              w_out (n, E, ff, d) (see below); shared_w_gate, shared_w_in
              (n, d, S ff); shared_w_out (n, S ff, d)

Each projection has std 1/sqrt(fan_in), the router too, so a logit has a
standard deviation near 1; a routed expert's w_out has 1/(ROUTED_OUT x
sqrt(ff)). With random weights and a random router (scores near 1/R, 16 x
1/160 here), bf16 rounding carries a token across a top-6 boundary in a
few per cent of token-layers, and each crossing moves that token's hidden
state by an expert's output times its weight (routed_scale x its score).
Unscaled (ROUTED_OUT 1), those jumps reach the float8 control's own gap,
so no limit tells the two apart; with the routed scale wholly taken back
out (ROUTED_OUT 16), the held experts add so little that zeroing them
passes the comparison. At 8 the sound gap stays well under the float8
control's, and held experts zeroed, the routed scale dropped, or the held
range shifted by one each read well above it (PERF.md section 6).
Everything is drawn in the configuration's ``param_dtype`` (bf16), the
router included.

Counts: a multiply-add counts as two operations; weights and the latent
cache are bf16 (2 bytes). Decode is counted in the absorbed form the
program uses (q_nope W_uk against the latent, W_uv after the weighted
sum); prefill in the explicit form. The routed work is what the
algorithm needs, not what the program computes: under uniform routing a
token sends ``top_k x E / R`` assignments to the held experts, and n
tokens touch ``E x (1 - (1 - top_k / R)^n)`` of them, each read once
(``decode_call``, ``prefill_flops``). ``moe_call`` counts from the
program's own counters instead. What the program does beyond the
algorithm (every held expert run on every token, the padded cache read
to ``max_seq``, the empty slots decoded) is not counted.
"""
from __future__ import annotations

import functools
from typing import Dict, Iterable, List, Tuple

BYTES = 2  # bf16 weights and cache

# a routed expert's w_out std is 1/(ROUTED_OUT x sqrt(ff)): see above
ROUTED_OUT = 8.0

# the widths of the CPU smoke runs; MLA keeps the program's (published)
# widths, which a file cannot set
SMOKE = {"num_layers": 3, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
         "vocab_size": 256, "dense_prefix_ff": 96,
         "moe": {"num_experts": 4, "router_experts": 64, "first_expert": 8,
                 "top_k": 6, "expert_groups": 8, "top_k_groups": 3,
                 "routed_scale": 16.0, "renormalize_top_k": False,
                 "expert_ff": 32, "num_shared_experts": 2, "shared_ff": 32}}


@functools.lru_cache(maxsize=None)
def _mla(items: Tuple) -> object:
    from bench.client import program_config
    return program_config(_unfreeze(items)).mla


def _freeze(m: Dict) -> Tuple:
    return tuple(sorted((k, _freeze(v) if isinstance(v, dict) else v)
                        for k, v in m.items()))


def _unfreeze(items: Tuple) -> Dict:
    return {k: _unfreeze(v) if isinstance(v, tuple) else v
            for k, v in items}


def mla(m: Dict):
    """The program's MLAConfig for the model ``m`` (raises where the
    program cannot take ``m``)."""
    return _mla(_freeze(m))


def _widths(m: Dict) -> Dict[str, int]:
    a = mla(m)
    e = m["moe"]
    return {"d": m["d_model"], "h": m["num_heads"], "qr": a.q_lora_rank,
            "r": a.kv_lora_rank, "nope": a.qk_nope_head_dim,
            "rope": a.qk_rope_head_dim, "v": a.v_head_dim,
            "V": m["vocab_size"], "P": m["dense_layer_prefix"],
            "M": m["num_layers"] - m["dense_layer_prefix"],
            "ffd": m["dense_prefix_ff"], "E": e["num_experts"],
            "R": e["router_experts"], "k": e["top_k"], "ff": e["expert_ff"],
            "sff": e["num_shared_experts"] * e["shared_ff"]}


# -- weights -----------------------------------------------------------------

def _attn(w: Dict, n: int) -> Dict[str, tuple]:
    d, h, qr, r = w["d"], w["h"], w["qr"], w["r"]
    return {"ln1": (n, d), "wq_a": (n, d, qr), "q_norm": (n, qr),
            "wq_b": (n, qr, h, w["nope"] + w["rope"]),
            "w_dkv": (n, d, r + w["rope"]), "kv_norm": (n, r),
            "w_uk": (n, r, h, w["nope"]), "w_uv": (n, r, h, w["v"]),
            "wo": (n, h, w["v"], d), "ln2": (n, d)}


def _std(name: str, shape):
    if name in ("final_norm", "ln1", "ln2", "q_norm", "kv_norm"):
        return None
    if name == "embed":
        return 1.0
    if name == "head":
        return shape[1] ** -0.5
    if name == "wo":
        return (shape[1] * shape[2]) ** -0.5
    if name == "w_out" and len(shape) == 4:
        return shape[2] ** -0.5 / ROUTED_OUT
    if name in ("w_gate", "w_in") and len(shape) == 4:
        return shape[2] ** -0.5      # (n, E, fan_in, out)
    return shape[1] ** -0.5          # (n, fan_in, ...)


def layout(m: Dict) -> List[Tuple[tuple, tuple, object]]:
    """Each tensor as (path in the weight tree, shape, std or None for
    ones), in draw order."""
    w = _widths(m)
    d, P, M, E = w["d"], w["P"], w["M"], w["E"]
    if mla(m).q_lora_rank == 0:
        raise ValueError("mla_moe lays out the low-rank q only")
    top = [("embed", (w["V"], d)), ("final_norm", (d,)),
           ("head", (w["V"], d))]
    dense = dict(_attn(w, P), w_gate=(P, d, w["ffd"]), w_in=(P, d, w["ffd"]),
                 w_out=(P, w["ffd"], d))
    moe = dict(_attn(w, M), router=(M, d, w["R"]),
               w_gate=(M, E, d, w["ff"]), w_in=(M, E, d, w["ff"]),
               w_out=(M, E, w["ff"], d), shared_w_gate=(M, d, w["sff"]),
               shared_w_in=(M, d, w["sff"]), shared_w_out=(M, w["sff"], d))
    out = [((n,), s, _std(n, s)) for n, s in top]
    for seg, ts in (("dense", dense), ("moe_layers", moe)):
        out += [((seg, n), s, _std(n.replace("shared_", ""), s))
                for n, s in ts.items()]
    return out


def _attn_params(ly: Dict) -> Dict:
    return {"ln1": {"scale": ly["ln1"]},
            "attn": {"wq_a": ly["wq_a"], "q_norm": {"scale": ly["q_norm"]},
                     "wq_b": ly["wq_b"], "w_dkv": ly["w_dkv"],
                     "kv_norm": {"scale": ly["kv_norm"]},
                     "w_uk": ly["w_uk"], "w_uv": ly["w_uv"],
                     "wo": ly["wo"]},
            "ln2": {"scale": ly["ln2"]}}


def program_params(w: Dict, m: Dict) -> Dict:
    """The program's parameter tree (``repro.models.model.model_schema``:
    a ``dense_prefix`` segment, then a ``moe`` segment), sharing w's
    arrays."""
    dn, mo = w["dense"], w["moe_layers"]
    dense = dict(_attn_params(dn), mlp={"w_gate": dn["w_gate"],
                                        "w_in": dn["w_in"],
                                        "w_out": dn["w_out"]})
    moe = dict(_attn_params(mo), moe={
        "router": mo["router"], "w_gate": mo["w_gate"], "w_in": mo["w_in"],
        "w_out": mo["w_out"],
        "shared": {"w_gate": mo["shared_w_gate"], "w_in": mo["shared_w_in"],
                   "w_out": mo["shared_w_out"]}})
    return {"embed": {"tokens": w["embed"], "head": w["head"]},
            "final_norm": {"scale": w["final_norm"]},
            "segments": (dense, moe)}


# -- operations and bytes ----------------------------------------------------

def attn_params(m: Dict) -> int:
    """One layer's attention projections, every one a token multiplies by
    in the absorbed decode (W_uk and W_uv once per token and head)."""
    w = _widths(m)
    d, h = w["d"], w["h"]
    return (d * w["qr"] + w["qr"] * h * (w["nope"] + w["rope"])
            + d * (w["r"] + w["rope"]) + w["r"] * h * (w["nope"] + w["v"])
            + h * w["v"] * d)


def expert_params(m: Dict) -> int:
    w = _widths(m)
    return 3 * w["d"] * w["ff"]


def held_per_token(m: Dict) -> float:
    """Assignments a token sends to the held experts, per expert layer,
    under uniform routing: top_k x E / R."""
    w = _widths(m)
    return w["k"] * w["E"] / w["R"]


def touched(m: Dict, n: int) -> float:
    """Held experts that n tokens touch, per expert layer, under uniform
    routing: E x (1 - (1 - top_k / R)^n)."""
    w = _widths(m)
    return w["E"] * (1.0 - (1.0 - w["k"] / w["R"]) ** n)


def _fixed(m: Dict) -> Dict[str, int]:
    """Parameters per token and per call: the dense layers' MLP, the
    expert layers' router and shared experts, the head."""
    w = _widths(m)
    d = w["d"]
    return {"dense_mlp": 3 * d * w["ffd"], "router": d * w["R"],
            "shared": 3 * d * w["sff"], "head": w["V"] * d}


def matmul_params(m: Dict) -> float:
    """Parameters a token that yields logits multiplies by (the routed
    experts' expected share: held_per_token)."""
    w, f = _widths(m), _fixed(m)
    return (m["num_layers"] * attn_params(m) + w["P"] * f["dense_mlp"]
            + w["M"] * (f["router"] + f["shared"]
                        + held_per_token(m) * expert_params(m))
            + f["head"])


def latent_bytes_per_token(m: Dict) -> int:
    w = _widths(m)
    return m["num_layers"] * (w["r"] + w["rope"]) * BYTES


def weight_bytes(m: Dict, n: int) -> float:
    """Every weight a call over n tokens reads once: attention, norms, the
    dense MLP, router and shared experts, the held experts the tokens
    touch, the final norm and the head (embedding rows per token)."""
    w, f = _widths(m), _fixed(m)
    d = w["d"]
    norms = 2 * d + w["qr"] + w["r"]
    params = (m["num_layers"] * (attn_params(m) + norms)
              + w["P"] * f["dense_mlp"]
              + w["M"] * (f["router"] + f["shared"]
                          + touched(m, n) * expert_params(m))
              + d + f["head"])
    return params * BYTES


def decode_call(m: Dict, positions: Iterable[int]) -> Dict[str, float]:
    """One decode step over the active slots, each writing its latent row
    at ``pos`` and attending over positions 0..pos: the latent cache is
    read up to each slot's own position, not to the padded length."""
    pos = list(positions)
    w = _widths(m)
    n = len(pos)
    # absorbed scores (latent + rope) and the weighted latent sum, per
    # head and context position, in every layer
    per_ctx = 2 * w["h"] * (2 * w["r"] + w["rope"]) * m["num_layers"]
    flops = sum(2 * matmul_params(m) + per_ctx * (p + 1) for p in pos)
    lat = latent_bytes_per_token(m)
    nbytes = (weight_bytes(m, n) + sum(p * lat + lat for p in pos)
              + n * w["d"] * BYTES)
    return {"flops": float(flops), "bytes": float(nbytes)}


def prefill_flops(m: Dict, n: int) -> float:
    """A prompt of n tokens in the explicit form: every layer's
    projections at every position (k_nope and v up from the latent),
    causal attention over the positions before each, the held experts'
    expected share of the routed work, and the head once, for the last
    position."""
    w, f = _widths(m), _fixed(m)
    layers = (m["num_layers"] * attn_params(m) + w["P"] * f["dense_mlp"]
              + w["M"] * (f["router"] + f["shared"]
                          + held_per_token(m) * expert_params(m)))
    attn = 2 * m["num_layers"] * w["h"] * (w["nope"] + w["rope"] + w["v"]) \
        * n * (n + 1) // 2
    return float(2 * layers * n + attn + 2 * f["head"])


def moe_call(m: Dict, tokens: float, held_assignments: float,
             experts_touched: float, calls: float = 1) -> Dict[str, float]:
    """The expert layers' work, from the program's counters: ``tokens``
    (token, layer) pairs through an expert layer, ``held_assignments``
    of them routed to a held expert, ``experts_touched`` (call, layer,
    held expert) triples with a token, over ``calls`` (call, layer)
    pairs. Router and shared experts run on every token and are read
    once a call; a held expert's weights are read once a call that
    touches it; each token's activation is read and written."""
    f = _fixed(m)
    d = _widths(m)["d"]
    flops = 2 * (tokens * (f["router"] + f["shared"])
                 + held_assignments * expert_params(m))
    nbytes = BYTES * (calls * (f["router"] + f["shared"])
                      + experts_touched * expert_params(m)
                      + 2 * tokens * d)
    return {"flops": float(flops), "bytes": float(nbytes)}
