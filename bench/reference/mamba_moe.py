"""Plain reference of Nemotron-H's blocks at one chip's share of its expert
layers (arXiv:2504.03624; config.json of
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, the
NemotronH modelling code).

The blocks follow the pattern's letters (``layer_pattern``, the first
``num_layers``), each ``x + mixer(RMSNorm(x))`` with eps ``norm_eps``:

  M  Mamba-2: z = x W_z; xBC = x [W_x | W_B | W_C] (the published
     in_proj's columns, split); dt = x W_dt; a depthwise causal conv of
     width w over xBC with a bias, then SiLU; x in heads of P, B and C in
     G groups of N, head h reading group h // (H / G); per position t and
     head h, with dt_h = softplus(dt + dt_bias), A = -exp(A_log):
        s_t = exp(dt_h A_h) s_{t-1} + dt_h x_t B_t^T     (P x N)
        y_t = s_t C_t + D_h x_t
     the recurrence run position by position (not the chunked SSD);
     y = RMSNorm per group of the inner width of y * silu(z); y W_out.
  E  router logits x W_r in f32 over all R outputs, s = sigmoid(logits);
     the top_k are chosen on s + correction_bias and weighted by s alone,
     divided by their sum, times routed_scale;
     y = sum over the held chosen e of w_e down_e(relu(up_e x)^2)
         + down_s(relu(up_s x)^2)   (the shared expert)
  *  GQA: query head h reads key/value head h // (H / KV), scale
     1/sqrt(hd), causal softmax, no positional encoding; out W_o.

then a final RMSNorm and the untied head. The held experts are
``first_expert`` .. ``first_expert + num_experts - 1`` of the router's R;
what the other experts would add is left out, as the program leaves it
out (the chip that holds them adds it).

Everything is float32 at ``Precision.HIGHEST`` over one whole sequence: no
cache, no batching, no kernels. It imports nothing of the program. It
reads the weights that the benchmark made (``bench/weights.py``), each
mixer's A_log and dt_bias made from its draws as the architecture says
(``bench/arch/mamba_moe.py`` ``mixer_constants``), upcast one block at a
time, attention a key/value head at a time and the head a block of
positions at a time, so that it fits beside them.

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

from bench.arch.mamba_moe import mixer_constants

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
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


def _conv(u, w, b):
    """Depthwise causal conv: y[t] = sum_j w[j] u[t - W + 1 + j] + b,
    zeros before the first position; u (S, c), w (W, c)."""
    W = w.shape[0]
    up = jnp.pad(u, ((W - 1, 0), (0, 0)))
    return sum(up[j:j + u.shape[0]] * w[j].astype(jnp.float32)
               for j in range(W)) + b.astype(jnp.float32)


def _mixer(p, a, m, quant):
    s = m["ssm"]
    H, P, G, N = s["num_heads"], s["head_dim"], s["n_groups"], \
        s["state_dim"]
    S = a.shape[0]

    def proj(name):
        return _mm("sd,de->se", a, p[name], quant, (1,), (0,))
    z, dt = proj("w_z"), proj("w_dt")
    x, B, C = (jax.nn.silu(_conv(proj(f"w_{k}"), p[f"conv_{k}"],
                                 p[f"conv_bias_{k}"])) for k in "xBC")
    a_log, dt_bias = mixer_constants(p)
    A = -jnp.exp(a_log)                                      # (H,)
    dt = jax.nn.softplus(dt + dt_bias)                       # (S, H)
    x = x.reshape(S, H, P)
    head_group = jnp.arange(H) // (H // G)
    Bh = B.reshape(S, G, N)[:, head_group]                   # (S, H, N)
    Ch = C.reshape(S, G, N)[:, head_group]

    def step(st, t):
        x_t, dt_t, B_t, C_t = t
        st = st * jnp.exp(dt_t * A)[:, None, None] \
            + (dt_t[:, None] * x_t)[..., None] * B_t[:, None, :]
        return st, jnp.einsum("hpn,hn->hp", st, C_t, precision=HI)
    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (x, dt, Bh, Ch))
    y = (y + p["D"].astype(jnp.float32)[:, None] * x).reshape(S, H * P)
    g = (y * jax.nn.silu(z)).reshape(S, G, -1)
    g = _rms(g, jnp.ones(()), m["norm_eps"]).reshape(S, H * P) \
        * p["norm"].astype(jnp.float32)
    return _mm("se,ed->sd", g, p["w_out"], quant, (1,), (0,))


def route(b, router, bias, mo, quant=None):
    """(weights (S, E_held)) of each token for each held expert: the
    top-k of sigmoid(logits) + bias over all of the router's outputs,
    weighted by the sigmoid alone, zero where a held expert is not among a
    token's top-k."""
    s = jax.nn.sigmoid(_mm("sd,de->se", b, router, quant, (1,), (0,)))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), mo["top_k"])
    top = jnp.take_along_axis(s, idx, axis=-1)
    if mo["renormalize_top_k"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * mo["routed_scale"]
    held = jnp.arange(mo["num_experts"]) + mo["first_expert"]
    hit = idx[..., None] == held                                # (S,k,E)
    return jnp.sum(jnp.where(hit, top[..., None], 0.0), axis=1)


def experts(b, p, mo, quant):
    """The held experts' part and the shared expert, for tokens b."""
    wts = route(b, p["router"], p["correction_bias"], mo, quant)   # (S, E)
    h = jnp.square(jax.nn.relu(
        _mm("sd,edf->esf", b, p["w_in"], quant, (1,), (1,))))
    routed = _mm("esf,efd->sd", h * wts.T[..., None], p["w_out"], quant,
                 (0, 2), (1,))
    hs = jnp.square(jax.nn.relu(
        _mm("sd,df->sf", b, p["shared_w_in"], quant, (1,), (0,))))
    return routed + _mm("sf,fd->sd", hs, p["shared_w_out"], quant, (1,),
                        (0,))


def _attention(p, a, quant):
    S = a.shape[0]
    H, hd = p["wq"].shape[1:]
    KV = p["wk"].shape[1]
    q = _mm("sd,dhk->shk", a, p["wq"], quant, (1,), (0,))
    k = _mm("sd,dhk->shk", a, p["wk"], quant, (1,), (0,))
    v = _mm("sd,dhk->shk", a, p["wv"], quant, (1,), (0,))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]

    def kv_head(args):                 # the query heads that read one KV
        qg, kh, vh = args              # (S, g, hd), (S, hd), (S, hd)
        sc = jnp.einsum("qgk,tk->gqt", qg, kh, precision=HI) / math.sqrt(hd)
        pr = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("gqt,tk->qgk", pr, vh, precision=HI)
    o = jax.lax.map(kv_head, (jnp.moveaxis(q.reshape(S, KV, H // KV, hd),
                                           1, 0),
                              jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    o = jnp.moveaxis(o, 0, 1).reshape(S, H, hd)
    return _mm("shk,hkd->sd", o, p["wo"], quant, (1, 2), (0, 1))


def logits_stats(w: Dict, m: Dict, tokens, probe, quant: Optional[str] = None):
    """Top logit, first token and the logits of ``probe`` (k, S) at each
    position of ``tokens`` (S,), from the float32 forward."""
    s = tokens.shape[0]
    eps = m["norm_eps"]
    x = w["embed"][tokens].astype(jnp.float32)
    for i, kind in enumerate(m["layer_pattern"][:m["num_layers"]]):
        p = jax.tree.map(lambda t: t[0], w["blocks"][f"{i:02d}"])
        a = _rms(x, p["ln1"], eps)
        if kind == "M":
            x = x + _mixer(p, a, m, quant)
        elif kind == "E":
            x = x + experts(a, p, m["moe"], quant)
        else:
            x = x + _attention(p, a, quant)
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
