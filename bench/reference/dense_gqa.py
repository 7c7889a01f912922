"""Plain reference of the dense GQA decoder that both configurations run.

Written from the published description (llama-style, as InternLM2 and
Granite-8B-Code are): token embedding; per layer a pre-RMSNorm
(eps 1e-5) attention with rotary positions (rotate-half, base
``rope_theta``) where query head h reads key/value head h // (H / KV),
scaled by 1/sqrt(hd), causal softmax, output projection, residual; then a
pre-RMSNorm gated MLP, silu(x W_gate) * (x W_in) W_out, residual; a final
RMSNorm and the vocabulary head (the embedding when tied).

Everything is float32 at ``Precision.HIGHEST`` over one whole sequence: no
cache, no batching, no kernels. It imports nothing of the program; it
reads the weights that the benchmark made (``bench/weights.py``), upcast
one layer at a time inside a scan so that it fits beside them.

``quant="fp8"`` is the control: the same forward with both operands of
every matrix product rounded to float8 e4m3 (weights scaled per output
channel, activations per row), the precision below the configuration's
bf16.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


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


def _rms(x, scale, eps=1e-5):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freq           # (S, half)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def logits(w: Dict, m: Dict, tokens, quant: Optional[str] = None):
    """Float32 logits (S, V) of one sequence ``tokens`` (S,)."""
    s = tokens.shape[0]
    h, kv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    pos = jnp.arange(s)
    causal = pos[:, None] >= pos[None, :]
    x = w["embed"][tokens].astype(jnp.float32)

    def layer(x, p):
        a = _rms(x, p["ln1"])
        q = _mm("sd,dhk->shk", a, p["wq"], quant, (1,), (0,))
        k = _mm("sd,dhk->shk", a, p["wk"], quant, (1,), (0,))
        v = _mm("sd,dhk->shk", a, p["wv"], quant, (1,), (0,))
        q, k = _rope(q, pos, m["rope_theta"]), _rope(k, pos, m["rope_theta"])
        k = jnp.repeat(k, h // kv, axis=1)
        v = jnp.repeat(v, h // kv, axis=1)
        sc = jnp.einsum("qhk,thk->hqt", q, k, precision=HI) / hd ** 0.5
        pr = jax.nn.softmax(jnp.where(causal[None], sc, -jnp.inf), axis=-1)
        o = jnp.einsum("hqt,thk->qhk", pr, v, precision=HI)
        x = x + _mm("qhk,hkd->qd", o, p["wo"], quant, (1, 2), (0, 1))
        b = _rms(x, p["ln2"])
        g = _mm("sd,df->sf", b, p["w_gate"], quant, (1,), (0,))
        u = _mm("sd,df->sf", b, p["w_in"], quant, (1,), (0,))
        y = _mm("sf,fd->sd", jax.nn.silu(g) * u, p["w_out"], quant,
                (1,), (0,))
        return x + y, None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = _rms(x, w["final_norm"])
    head = w.get("head", w["embed"])
    return _mm("sd,vd->sv", x, head, quant, (1,), (1,))


@functools.partial(jax.jit, static_argnames=("mkey", "quant"))
def _stats(w, tokens, probe, mkey, quant):
    m = dict(mkey)
    lg = logits(w, m, tokens, quant)
    top = jnp.max(lg, -1)
    first = jnp.argmax(lg, -1).astype(jnp.int32)
    at = jnp.take_along_axis(lg[None], probe[..., None], axis=-1)[..., 0]
    return top, first, at


def stats(w: Dict, m: Dict, tokens, probe, quant: Optional[str] = None):
    """For each position of ``tokens`` (S,): the top logit, the token that
    comes first, and the logits of the tokens in ``probe`` (k, S)."""
    mkey = tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))
    return _stats(w, tokens, probe, mkey, quant)
