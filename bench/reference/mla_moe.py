"""Plain reference of DeepSeek-V2's block at one chip's share of its
expert layers (arXiv:2405.04434; config.json of
https://huggingface.co/deepseek-ai/DeepSeek-V2).

Per layer, with every RMSNorm at eps ``norm_eps``:

  latent attention (§2.1):
    c_q   = RMSNorm_q(x W_qa);  q = c_q W_qb -> per head q_nope | q_pe
    ckv   = x W_dkv -> c_kv | k_pe (one rope key shared by all heads)
    c_kv  = RMSNorm_kv(c_kv);   k_nope = c_kv W_uk;  v = c_kv W_uv
    q_pe, k_pe rotated by YaRN rope at the token's position
    score = (q_nope.k_nope + q_pe.k_pe) * mscale^2 / sqrt(nope + rope),
    mscale = 0.1 * mscale_all_dim * ln(factor) + 1; causal softmax;
    out = concat_h(softmax(score) v) W_o
  then, in the first ``dense_layer_prefix`` layers, a gated MLP
  silu(x W_gate) * (x W_in) W_out; in the rest the expert layer:
    s = softmax(x W_r) over all R router outputs
    group-limited greedy: each of G equal groups scores its best expert,
    the ``top_k_groups`` best groups are kept, other scores set to 0;
    (w_k, e_k) = top_k of those; w_k = s_{e_k} * routed_scale (divided by
    their sum first where ``renormalize_top_k``)
    y = sum over k with e_k held of w_k FFN_{e_k}(x) + FFN_shared(x)
  each as a residual after a pre-RMSNorm; a final RMSNorm and the
  untied head.

The held experts are ``first_expert`` .. ``first_expert + num_experts -
1`` of the router's R; what the other experts would add is left out, as
the program leaves it out (the chip that holds them adds it). YaRN
(DeepSeek-V2's published rope_scaling): factor 40 over 4096 original
positions, beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 0.707,
base ``rope_theta``; the interpolated and original frequencies blended
by a linear ramp between the correction dimensions, cos and sin scaled
by mscale(factor, mscale) / mscale(factor, mscale_all_dim) = 1.

One departure from the published code: it interleaves the 64 rope
channels into pairs before rotating by halves. On seeded random weights
that is a fixed relabelling of W_qb's and W_dkv's rope columns, so this
reference, like the program, rotates the channels as they stand
(rotate-half), in the program's column order.

Widths come from the weights' shapes; routing from ``m["moe"]``.
Everything is float32 at ``Precision.HIGHEST`` over one whole sequence:
no cache, no batching, no kernels. It imports nothing of the program. It
reads the weights that the benchmark made (``bench/weights.py``), upcast
one layer at a time inside a scan, attention a block of heads at a time
and the head a block of positions at a time, so that it fits beside
them.

``quant="fp8"`` is the control: the same forward with both operands of
every weight's matrix product (router and experts included) rounded to
float8 e4m3 (weights scaled per output channel, activations per row),
the precision below the configuration's bf16.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
YARN = {"factor": 40.0, "original_max_position_embeddings": 4096,
        "beta_fast": 32.0, "beta_slow": 1.0, "mscale": 0.707,
        "mscale_all_dim": 0.707}
HEAD_BLOCK = 16      # attention heads a block
POS_BLOCK = 256      # positions a block of the vocabulary head


def _fp8(x, axes):
    """Round x to float8 e4m3 with one scale per slice along ``axes``."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, x, w, quant, x_axes, w_axes):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if quant == "fp8":
        x, w = _fp8(x, x_axes), _fp8(w, w_axes)
    return jnp.einsum(spec, x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float) -> np.ndarray:
    """YaRN's rope frequencies for ``dim`` rotary channels."""
    y = YARN
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / y["factor"]

    def corr(rot):
        return dim * math.log(y["original_max_position_embeddings"]
                              / (rot * 2 * math.pi)) / (2 * math.log(base))
    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp
    return inter * (1 - keep) + extra * keep


def _rope(x, pos, inv_freq, scale):
    half = x.shape[-1] // 2
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv_freq,
                                                         jnp.float32)
    c = (jnp.cos(ang) * scale)[:, None]
    s = (jnp.sin(ang) * scale)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(p, a, pos, m, quant):
    eps = m["norm_eps"]
    r = p["kv_norm"].shape[-1]
    h, nope = p["w_uk"].shape[1], p["w_uk"].shape[2]
    rope = p["wq_b"].shape[-1] - nope
    y = YARN
    inv = yarn_inv_freq(rope, m["rope_theta"])
    cs = _mscale(y["factor"], y["mscale"]) / \
        _mscale(y["factor"], y["mscale_all_dim"])
    scale = _mscale(y["factor"], y["mscale_all_dim"]) ** 2 \
        / math.sqrt(nope + rope)

    c_q = _rms(_mm("sd,dr->sr", a, p["wq_a"], quant, (1,), (0,)),
               p["q_norm"], eps)
    q = _mm("sr,rhk->shk", c_q, p["wq_b"], quant, (1,), (0,))
    ckv = _mm("sd,dr->sr", a, p["w_dkv"], quant, (1,), (0,))
    c_kv = _rms(ckv[:, :r], p["kv_norm"], eps)
    k_pe = _rope(ckv[:, None, r:], pos, inv, cs)                 # (S,1,rope)
    q_nope, q_pe = q[..., :nope], _rope(q[..., nope:], pos, inv, cs)
    k_nope = _mm("sr,rhk->shk", c_kv, p["w_uk"], quant, (1,), (0,))
    v = _mm("sr,rhk->shk", c_kv, p["w_uv"], quant, (1,), (0,))
    causal = pos[:, None] >= pos[None, :]

    hb = math.gcd(h, HEAD_BLOCK)

    def heads(args):
        qn, qp, kn, vv = args                      # (S, hb, .) each
        sc = (jnp.einsum("qhk,thk->hqt", qn, kn, precision=HI)
              + jnp.einsum("qhk,tk->hqt", qp, k_pe[:, 0], precision=HI)) \
            * scale
        pr = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thk->qhk", pr, vv, precision=HI)

    def blocks(t):
        return jnp.moveaxis(t.reshape(t.shape[0], h // hb, hb, -1), 1, 0)
    o = jax.lax.map(heads, (blocks(q_nope), blocks(q_pe), blocks(k_nope),
                            blocks(v)))                      # (h/hb,S,hb,v)
    o = jnp.moveaxis(o, 0, 1).reshape(a.shape[0], h, -1)
    return _mm("shk,hkd->sd", o, p["wo"], quant, (1, 2), (0, 1))


def _mlp(b, w_gate, w_in, w_out, quant):
    g = _mm("sd,df->sf", b, w_gate, quant, (1,), (0,))
    u = _mm("sd,df->sf", b, w_in, quant, (1,), (0,))
    return _mm("sf,fd->sd", jax.nn.silu(g) * u, w_out, quant, (1,), (0,))


def route(b, router, mo, quant=None):
    """(weights (S, E_held)) of each token for each held expert: the
    group-limited greedy top-k over all of the router's outputs, zero
    where a held expert is not among a token's top-k."""
    s = jax.nn.softmax(_mm("sd,de->se", b, router, quant, (1,), (0,)), -1)
    n, R = s.shape
    G = mo["expert_groups"]
    if G > 1:
        grouped = s.reshape(n, G, R // G)
        _, keep = jax.lax.top_k(jnp.max(grouped, -1), mo["top_k_groups"])
        kept = jnp.any(jax.nn.one_hot(keep, G, dtype=bool), axis=1)
        s = jnp.where(kept[..., None], grouped, 0.0).reshape(n, R)
    top, idx = jax.lax.top_k(s, mo["top_k"])
    if mo["renormalize_top_k"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * mo["routed_scale"]
    held = jnp.arange(mo["num_experts"]) + mo["first_expert"]
    hit = idx[..., None] == held                                # (S,k,E)
    return jnp.sum(jnp.where(hit, top[..., None], 0.0), axis=1)


def _experts(b, p, mo, quant):
    wts = route(b, p["router"], mo, quant)                       # (S, E)
    g = _mm("sd,edf->esf", b, p["w_gate"], quant, (1,), (1,))
    u = _mm("sd,edf->esf", b, p["w_in"], quant, (1,), (1,))
    hid = jax.nn.silu(g) * u * wts.T[..., None]
    return _mm("esf,efd->sd", hid, p["w_out"], quant, (0, 2), (1,))


def logits_stats(w: Dict, m: Dict, tokens, probe, quant: Optional[str] = None):
    """Top logit, first token and the logits of ``probe`` (k, S) at each
    position of ``tokens`` (S,), from the float32 forward."""
    s = tokens.shape[0]
    eps = m["norm_eps"]
    mo = m["moe"]
    pos = jnp.arange(s)
    x = w["embed"][tokens].astype(jnp.float32)

    def dense(x, p):
        x = x + _attention(p, _rms(x, p["ln1"], eps), pos, m, quant)
        b = _rms(x, p["ln2"], eps)
        return x + _mlp(b, p["w_gate"], p["w_in"], p["w_out"], quant), None

    def expert(x, p):
        x = x + _attention(p, _rms(x, p["ln1"], eps), pos, m, quant)
        b = _rms(x, p["ln2"], eps)
        y = _experts(b, p, mo, quant) + _mlp(
            b, p["shared_w_gate"], p["shared_w_in"], p["shared_w_out"],
            quant)
        return x + y, None

    x, _ = jax.lax.scan(dense, x, w["dense"])
    x, _ = jax.lax.scan(expert, x, w["moe_layers"])
    x = _rms(x, w["final_norm"], eps)

    pb = math.gcd(s, POS_BLOCK)

    def head(args):
        xb, pr = args
        lg = _mm("sd,vd->sv", xb, w["head"], quant, (1,), (1,))
        at = jnp.take_along_axis(lg[None], pr[..., None], axis=-1)[..., 0]
        return jnp.max(lg, -1), jnp.argmax(lg, -1).astype(jnp.int32), at

    k = probe.shape[0]
    top, first, at = jax.lax.map(head, (
        x.reshape(s // pb, pb, -1),
        jnp.moveaxis(probe.reshape(k, s // pb, pb), 1, 0)))
    return (top.reshape(s), first.reshape(s),
            jnp.moveaxis(at, 0, 1).reshape(k, s))


def _freeze(m: Dict):
    return tuple(sorted((k, _freeze(v) if isinstance(v, dict) else v)
                        for k, v in m.items()
                        if isinstance(v, (int, float, str, bool, dict))))


def _unfreeze(items) -> Dict:
    return {k: _unfreeze(v) if isinstance(v, tuple) else v
            for k, v in items}


@functools.partial(jax.jit, static_argnames=("mkey", "quant"))
def _stats(w, tokens, probe, mkey, quant):
    return logits_stats(w, _unfreeze(mkey), tokens, probe, quant)


def stats(w: Dict, m: Dict, tokens, probe, quant: Optional[str] = None):
    """For each position of ``tokens`` (S,): the top logit, the token that
    comes first, and the logits of the tokens in ``probe`` (k, S)."""
    return _stats(w, tokens, probe, _freeze(m), quant)
