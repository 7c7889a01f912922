"""Nemotron-H's blocks, as the program runs them: a published layer
pattern of Mamba-2 mixers (M), expert layers (E) and GQA attention (*),
one mixer a block, each ``x + mixer(RMSNorm(x))``; of the router's
``moe.router_experts`` this chip holds ``moe.num_experts`` routed experts
(from ``moe.first_expert``), plus the shared expert. The benchmark's
weight layout, the program's parameter tree, and the operations and
bytes that a call needs, from shapes alone.

Weight layout (``layout``, in draw order): embed (V, d) std 1;
final_norm (d,) ones; head (V, d) std 1/sqrt(d); then each block ``i`` of
the pattern's first ``num_layers`` under ``blocks/<i>``, every tensor with
a leading axis of 1 (the program runs each block as a segment of one):

  M  ln1 (d) ones; w_x, w_z (d, di); w_B, w_C (d, G N); w_dt (d, heads);
     conv_x (w, di), conv_B, conv_C (w, G N) and their conv_bias_* (di or
     G N); a_draw, dt_draw (heads) std 1; D (heads) ones; norm (di) ones;
     w_out (di, d)
  E  ln1; router (d, R); correction_bias (R) std BIAS_STD; w_in (E, d,
     ff); w_out (E, ff, d) (see below); shared_w_in (d, S ff);
     shared_w_out (S ff, d)
  *  ln1; wq (d, H, hd); wk, wv (d, KV, hd); wo (H, hd, d)

Each projection has std 1/sqrt(fan_in), the router too; the conv's
weights and biases 1/sqrt(3 w), the std of PyTorch's default conv init.
``mixer_constants`` turns a mixer's two standard normal draws into its
A_log and dt_bias as Mamba-2's published initialisation draws them:
A uniform in [1, 16], dt log-uniform in [dt_min, dt_max] floored at
DT_FLOOR, dt_bias its inverse softplus. The correction bias is drawn, not
zero, so that choosing experts on the score alone (the bias dropped)
shows. A routed expert's w_out has 1/(ROUTED_OUT x sqrt(ff)): with a
random router bf16 rounding carries a token across a top-6 boundary in a
few per cent of token-layers, and each crossing moves that token by an
expert's output times its weight (PERF.md section 6). Everything is
drawn in the configuration's ``param_dtype`` (bf16).

Counts: a multiply-add counts as two operations; weights, conv tails and
K/V are bf16 (2 bytes), the recurrent state f32 (4 bytes), read and
written once a decode. A state element's update is five operations
(decay, x B, their sum, times C, the sum over the state). Prefill is
counted as the recurrence (linear in the prompt), not the chunked
quadratic form the program runs. The routed work is what the algorithm
needs, as in ``mla_moe``: under uniform routing a token sends
``top_k x E / R`` assignments to the held experts, and n tokens touch
``E x (1 - (1 - top_k / R)^n)`` of them. What the program does beyond
the algorithm (every held expert run on every token, the empty slots
decoded, the padded chunk) is not counted.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, List, Tuple

BYTES = 2          # bf16 weights, conv tails and K/V
STATE_BYTES = 4    # f32 recurrent state
UPDATE_OPS = 5     # operations a state element's decode update takes

# a routed expert's w_out std is 1/(ROUTED_OUT x sqrt(ff)): see above
ROUTED_OUT = 8.0
BIAS_STD = 0.2     # the correction bias's draw
A_RANGE = (1.0, 16.0)
DT_MIN, DT_MAX, DT_FLOOR = 0.001, 0.1, 1e-4

# the widths of the CPU smoke runs: every block kind, 2 B/C groups, a held
# share of the router's experts
SMOKE = {"num_layers": 6, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 16, "vocab_size": 256,
         "ssm": {"state_dim": 16, "head_dim": 16, "num_heads": 8,
                 "n_groups": 2, "conv_width": 4, "conv_bias": True,
                 "chunk": 32},
         "moe": {"num_experts": 4, "router_experts": 32, "first_expert": 4,
                 "top_k": 6, "expert_ff": 32, "num_shared_experts": 1,
                 "shared_ff": 64, "routed_scale": 2.5,
                 "renormalize_top_k": True, "score": "sigmoid"}}


def blocks(m: Dict) -> str:
    """The pattern's letter of each block the model runs."""
    return m["layer_pattern"][:m["num_layers"]]


def _widths(m: Dict) -> Dict[str, int]:
    s, e = m["ssm"], m["moe"]
    if not s["conv_bias"]:
        raise ValueError("mamba_moe lays out a conv with a bias only")
    gn = s["n_groups"] * s["state_dim"]
    di = s["num_heads"] * s["head_dim"]
    return {"d": m["d_model"], "V": m["vocab_size"], "H": m["num_heads"],
            "KV": m["num_kv_heads"], "hd": m["head_dim"],
            "sh": s["num_heads"], "sp": s["head_dim"], "N": s["state_dim"],
            "di": di, "gn": gn, "w": s["conv_width"], "ch": di + 2 * gn,
            "E": e["num_experts"], "R": e["router_experts"],
            "k": e["top_k"], "ff": e["expert_ff"],
            "sff": e["num_shared_experts"] * e["shared_ff"]}


# -- weights -----------------------------------------------------------------

def _block(kind: str, w: Dict) -> List[Tuple[str, tuple, object]]:
    """(name, shape without the leading 1, std or None) of one block."""
    d = w["d"]
    out = [("ln1", (d,), None)]
    if kind == "M":
        di, gn, sh, cw = w["di"], w["gn"], w["sh"], w["w"]
        conv = (3 * cw) ** -0.5
        out += [("w_x", (d, di), d ** -0.5), ("w_z", (d, di), d ** -0.5),
                ("w_B", (d, gn), d ** -0.5), ("w_C", (d, gn), d ** -0.5),
                ("w_dt", (d, sh), d ** -0.5)]
        for k, c in (("x", di), ("B", gn), ("C", gn)):
            out += [(f"conv_{k}", (cw, c), conv), (f"conv_bias_{k}", (c,),
                                                    conv)]
        out += [("a_draw", (sh,), 1.0), ("dt_draw", (sh,), 1.0),
                ("D", (sh,), None), ("norm", (di,), None),
                ("w_out", (di, d), di ** -0.5)]
    elif kind == "E":
        E, ff, sff = w["E"], w["ff"], w["sff"]
        out += [("router", (d, w["R"]), d ** -0.5),
                ("correction_bias", (w["R"],), BIAS_STD),
                ("w_in", (E, d, ff), d ** -0.5),
                ("w_out", (E, ff, d), ff ** -0.5 / ROUTED_OUT),
                ("shared_w_in", (d, sff), d ** -0.5),
                ("shared_w_out", (sff, d), sff ** -0.5)]
    else:
        H, KV, hd = w["H"], w["KV"], w["hd"]
        out += [("wq", (d, H, hd), d ** -0.5), ("wk", (d, KV, hd), d ** -0.5),
                ("wv", (d, KV, hd), d ** -0.5),
                ("wo", (H, hd, d), (H * hd) ** -0.5)]
    return out


def layout(m: Dict) -> List[Tuple[tuple, tuple, object]]:
    """Each tensor as (path in the weight tree, shape, std or None for
    ones), in draw order."""
    w = _widths(m)
    d = w["d"]
    out = [(("embed",), (w["V"], d), 1.0), (("final_norm",), (d,), None),
           (("head",), (w["V"], d), d ** -0.5)]
    for i, kind in enumerate(blocks(m)):
        out += [(("blocks", f"{i:02d}", n), (1,) + s, std)
                for n, s, std in _block(kind, w)]
    return out


def mixer_constants(b: Dict):
    """A mixer's (A_log, dt_bias), float32, from its draws ``a_draw`` and
    ``dt_draw`` (standard normals, made uniform by their CDF):
    A = U[A_RANGE], dt = exp(U[log DT_MIN, log DT_MAX]) floored at
    DT_FLOOR, dt_bias = dt + log(1 - exp(-dt)) (softplus(dt_bias) = dt)."""
    import jax.numpy as jnp
    from jax.scipy.special import ndtr
    lo, hi = A_RANGE
    a_log = jnp.log(lo + (hi - lo) * ndtr(b["a_draw"].astype(jnp.float32)))
    lmin, lmax = math.log(DT_MIN), math.log(DT_MAX)
    dt = jnp.maximum(jnp.exp(lmin + (lmax - lmin) * ndtr(
        b["dt_draw"].astype(jnp.float32))), DT_FLOOR)
    return a_log, dt + jnp.log(-jnp.expm1(-dt))


def program_params(w: Dict, m: Dict) -> Dict:
    """The program's parameter tree (``repro.models.model.model_schema``:
    one segment of one block each), sharing w's arrays; each mixer's A_log
    and dt_bias are made from its draws (``mixer_constants``)."""
    segs = []
    for i, kind in enumerate(blocks(m)):
        b = w["blocks"][f"{i:02d}"]
        seg = {"ln1": {"scale": b["ln1"]}}
        if kind == "M":
            a_log, dt_bias = mixer_constants(b)
            seg["ssm"] = {k: v for k, v in b.items()
                          if k not in ("ln1", "a_draw", "dt_draw", "norm")}
            seg["ssm"].update(A_log=a_log, dt_bias=dt_bias,
                              norm={"scale": b["norm"]})
        elif kind == "E":
            seg["moe"] = {k: b[k] for k in ("router", "correction_bias",
                                            "w_in", "w_out")}
            seg["moe"]["shared"] = {"w_in": b["shared_w_in"],
                                    "w_out": b["shared_w_out"]}
        else:
            seg["attn"] = {k: b[k] for k in ("wq", "wk", "wv", "wo")}
        segs.append(seg)
    return {"embed": {"tokens": w["embed"], "head": w["head"]},
            "final_norm": {"scale": w["final_norm"]},
            "segments": tuple(segs)}


def param_count(m: Dict) -> int:
    """Parameters of the model as laid out (every block, embedding and
    head)."""
    w = _widths(m)
    per = {k: sum(math.prod(s) for _, s, _ in _block(k, w)) for k in "ME*"}
    return 2 * w["V"] * w["d"] + w["d"] + sum(per[k] for k in blocks(m))


# -- operations and bytes ----------------------------------------------------

def _counts(m: Dict) -> Dict[str, int]:
    return {k: blocks(m).count(k) for k in "ME*"}


def mixer_matmul(m: Dict) -> int:
    """One mixer's projection parameters, each multiplied once a token."""
    w = _widths(m)
    return w["d"] * (w["di"] + w["ch"] + w["sh"]) + w["di"] * w["d"]


def mixer_bytes(m: Dict) -> int:
    """One mixer's weights: projections, conv weights and biases, the
    gated norm, the pre-norm (bf16), and A_log, D, dt_bias (f32)."""
    w = _widths(m)
    return BYTES * (mixer_matmul(m) + (w["w"] + 1) * w["ch"] + w["di"]
                    + w["d"]) + STATE_BYTES * 3 * w["sh"]


def state_elems(m: Dict) -> int:
    """Recurrent state elements of one mixer and slot."""
    w = _widths(m)
    return w["sh"] * w["sp"] * w["N"]


def slot_state_bytes(m: Dict) -> int:
    """One mixer's state (f32) and conv tails (bf16) a slot."""
    w = _widths(m)
    return STATE_BYTES * state_elems(m) + BYTES * (w["w"] - 1) * w["ch"]


def _mixer_ops(m: Dict) -> int:
    """A token's operations in one mixer beyond its projections: the
    conv and the state's update and read-out."""
    w = _widths(m)
    return 2 * w["w"] * w["ch"] + UPDATE_OPS * state_elems(m)


def ssm_call(m: Dict, live_slots: float) -> Dict[str, float]:
    """The M layers' work in one decode over ``live_slots`` slots: their
    weights read once; each live slot's state and conv tails read and
    written, its activation read and written; projections, conv and state
    update."""
    w = _widths(m)
    L, n = _counts(m)["M"], live_slots
    flops = L * n * (2 * mixer_matmul(m) + _mixer_ops(m))
    nbytes = L * (mixer_bytes(m) + n * (2 * slot_state_bytes(m)
                                        + 2 * BYTES * w["d"]))
    return {"flops": float(flops), "bytes": float(nbytes)}


def attn_params(m: Dict) -> int:
    w = _widths(m)
    return 2 * w["d"] * w["hd"] * (w["H"] + w["KV"])


def expert_params(m: Dict) -> int:
    w = _widths(m)
    return 2 * w["d"] * w["ff"]


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
    """An expert layer's router and shared expert, and the head."""
    w = _widths(m)
    return {"router": w["d"] * w["R"], "shared": 2 * w["d"] * w["sff"],
            "head": w["V"] * w["d"]}


def matmul_params(m: Dict) -> float:
    """Parameters a token that yields logits multiplies by (the routed
    experts' expected share: held_per_token)."""
    c, f = _counts(m), _fixed(m)
    return (c["M"] * mixer_matmul(m) + c["*"] * attn_params(m)
            + c["E"] * (f["router"] + f["shared"]
                        + held_per_token(m) * expert_params(m))
            + f["head"])


def kv_bytes_per_token(m: Dict) -> int:
    """K and V of one position in every attention layer."""
    w = _widths(m)
    return _counts(m)["*"] * 2 * w["KV"] * w["hd"] * BYTES


def weight_bytes(m: Dict, n: int) -> float:
    """Every weight a call over n tokens reads once: mixers, attention,
    routers with their bias, shared experts, the held experts the tokens
    touch, the norms and the head (embedding rows per token)."""
    w, c, f = _widths(m), _counts(m), _fixed(m)
    d = w["d"]
    params = (c["*"] * (attn_params(m) + d)
              + c["E"] * (f["router"] + w["R"] + f["shared"] + d
                          + touched(m, n) * expert_params(m))
              + d + f["head"])
    return params * BYTES + c["M"] * mixer_bytes(m)


def decode_call(m: Dict, positions: Iterable[int]) -> Dict[str, float]:
    """One decode step over the active slots, each at ``pos``: every
    mixer's state and conv tails read and written, each attention layer's
    K/V read over positions 0..pos and the new row written."""
    pos = list(positions)
    w, c = _widths(m), _counts(m)
    n = len(pos)
    per_ctx = 4 * w["H"] * w["hd"] * c["*"]          # scores, weighted sum
    per_tok = 2 * matmul_params(m) + c["M"] * _mixer_ops(m)
    flops = sum(per_tok + per_ctx * (p + 1) for p in pos)
    kv = kv_bytes_per_token(m)
    nbytes = (weight_bytes(m, n) + n * w["d"] * BYTES
              + n * c["M"] * 2 * slot_state_bytes(m)
              + sum((p + 1) * kv + kv for p in pos))
    return {"flops": float(flops), "bytes": float(nbytes)}


def prefill_flops(m: Dict, n: int) -> float:
    """A prompt of n tokens: every block's projections at every position,
    the mixers' conv and recurrence, causal attention over the positions
    before each, the held experts' expected share of the routed work, and
    the head once, for the last position."""
    w, c, f = _widths(m), _counts(m), _fixed(m)
    layers = matmul_params(m) - f["head"]
    attn = 4 * c["*"] * w["H"] * w["hd"] * n * (n + 1) // 2
    return float(2 * layers * n + n * c["M"] * _mixer_ops(m) + attn
                 + 2 * f["head"])


def moe_call(m: Dict, tokens: float, held_assignments: float,
             experts_touched: float, calls: float = 1) -> Dict[str, float]:
    """The expert layers' work, from the program's counters: ``tokens``
    (token, layer) pairs through an expert layer, ``held_assignments``
    of them routed to a held expert, ``experts_touched`` (call, layer,
    held expert) triples with a token, over ``calls`` (call, layer)
    pairs. Router and shared expert run on every token and are read once
    a call; a held expert's weights are read once a call that touches it;
    each token's activation is read and written."""
    f = _fixed(m)
    w = _widths(m)
    flops = 2 * (tokens * (f["router"] + f["shared"])
                 + held_assignments * expert_params(m))
    nbytes = BYTES * (calls * (f["router"] + w["R"] + f["shared"])
                      + experts_touched * expert_params(m)
                      + 2 * tokens * w["d"])
    return {"flops": float(flops), "bytes": float(nbytes)}
