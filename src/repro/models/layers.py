"""Shared neural layers: norms, MLPs, rotary embeddings, vocab heads.

All parameters are declared as ``ParamDesc`` schemas with *logical* dims;
the sharding layer maps them onto whatever mesh the operator provides
(divisibility-aware). Compute is bf16 with f32 reductions.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.distribution.sharding import ParamDesc, ShardingCtx


def f32(x):
    return x.astype(jnp.float32)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def norm_schema(d: int, kind: str, dtype: str):
    if kind == "layernorm":
        return {"scale": ParamDesc((d,), ("none",), dtype, "ones"),
                "bias": ParamDesc((d,), ("none",), dtype, "zeros")}
    return {"scale": ParamDesc((d,), ("none",), dtype, "ones")}


def apply_norm(p, x, kind: str, eps: float = 1e-5):
    xf = f32(x)
    if kind == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * f32(p["scale"]) + f32(p["bias"])
    else:  # rmsnorm
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * f32(p["scale"])
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (gated / squared-relu / gelu)
# ---------------------------------------------------------------------------


def mlp_schema(d: int, ff: int, activation: str, dtype: str):
    s = {"w_in": ParamDesc((d, ff), ("embed", "ffn"), dtype),
         "w_out": ParamDesc((ff, d), ("ffn", "embed"), dtype)}
    if activation == "silu_glu":
        s["w_gate"] = ParamDesc((d, ff), ("embed", "ffn"), dtype)
    return s


def apply_mlp(p, x, activation: str, shd: Optional[ShardingCtx] = None):
    h = jnp.einsum("...d,df->...f", x, p["w_in"])
    if activation == "silu_glu":
        g = jnp.einsum("...d,df->...f", x, p["w_gate"])
        h = jax.nn.silu(f32(g)).astype(x.dtype) * h
    elif activation == "relu2":
        h = jnp.square(jax.nn.relu(f32(h))).astype(x.dtype)
    else:  # gelu
        h = jax.nn.gelu(f32(h)).astype(x.dtype)
    if shd is not None:
        h = shd.constrain(h, ("batch",) + (None,) * (h.ndim - 2) + ("ffn",))
    return jnp.einsum("...f,fd->...d", h, p["w_out"])


# ---------------------------------------------------------------------------
# Rotary position embedding (rotate-half convention)
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature term: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(head_dim: int, theta: float, mla) -> np.ndarray:
    """YaRN's rope frequencies (``mla``: an MLAConfig with rope_factor > 1).

    Channels that turn more than ``beta_fast`` times over the original
    context keep their frequency, those that turn fewer than
    ``beta_slow`` times are divided by the factor, and a linear ramp
    blends the two between those channels."""
    half = head_dim // 2
    extra = 1.0 / theta ** (np.arange(half, dtype=np.float64) * 2 / head_dim)
    inter = extra / mla.rope_factor
    orig = mla.rope_original_max_positions

    def corr(rotations):
        return head_dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(corr(mla.rope_beta_fast)), 0)
    high = min(math.ceil(corr(mla.rope_beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    keep = 1.0 - ramp
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def rope_tables(positions: jax.Array, head_dim: int, theta: float,
                mla=None):
    """positions: (...,) int -> (cos, sin) of shape positions.shape+(head_dim//2,).

    ``mla`` with ``rope_factor`` > 1: YaRN's frequencies, and cos/sin
    scaled by mscale(factor, mscale) / mscale(factor, mscale_all_dim)."""
    half = head_dim // 2
    if mla is not None and mla.rope_factor > 1.0:
        freq = jnp.asarray(yarn_inv_freq(head_dim, theta, mla))
        scale = yarn_mscale(mla.rope_factor, mla.rope_mscale) / \
            yarn_mscale(mla.rope_factor, mla.rope_mscale_all_dim)
    else:
        freq = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
        scale = 1.0
    angles = positions[..., None].astype(jnp.float32) * freq
    cos = jnp.cos(angles)
    sin = jnp.sin(angles)
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    return cos, sin


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array):
    """x: (..., heads, head_dim); cos/sin: broadcastable (..., head_dim//2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = jnp.expand_dims(cos, -2)   # broadcast over heads
    s = jnp.expand_dims(sin, -2)
    y1 = f32(x1) * c - f32(x2) * s
    y2 = f32(x2) * c + f32(x1) * s
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


def sinusoid_positions(positions: jax.Array, d_model: int):
    """Sinusoidal absolute position embedding (whisper-style stub)."""
    half = d_model // 2
    freq = jnp.exp(-jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# Embedding / LM head
# ---------------------------------------------------------------------------


def embed_schema(vocab: int, d: int, dtype: str, tie: bool):
    s = {"tokens": ParamDesc((vocab, d), ("vocab", "embed"), dtype,
                             init_scale=1.0)}
    if not tie:
        s["head"] = ParamDesc((vocab, d), ("vocab", "embed"), dtype)
    return s


def embed_tokens(p, tokens: jax.Array, dtype):
    return jnp.take(p["tokens"], tokens, axis=0).astype(dtype)


def lm_logits(p, x: jax.Array, shd: Optional[ShardingCtx] = None,
              softcap: float = 0.0):
    w = p.get("head", p["tokens"])
    logits = jnp.einsum("...d,vd->...v", x, w)
    if shd is not None:
        logits = shd.constrain(
            logits, ("batch",) + (None,) * (logits.ndim - 2) + ("vocab",))
    if softcap:
        logits = jnp.tanh(f32(logits) / softcap) * softcap
    return logits
