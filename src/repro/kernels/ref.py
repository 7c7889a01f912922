"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each ``*_ref`` mirrors its kernel's semantics exactly; tests sweep shapes and
dtypes asserting allclose between kernel (interpreted on the CPU) and oracle.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal=True, window=0, scale=None):
    """q: (B,H,S,d); k,v: (B,H,T,d). Full softmax attention."""
    b, h, s, d = q.shape
    t = k.shape[2]
    scale = scale or 1.0 / math.sqrt(d)
    logits = jnp.einsum("bhsd,bhtd->bhst", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    q_pos = jnp.arange(s)[:, None]
    k_pos = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), bool)
    if causal:
        mask &= q_pos >= k_pos
    if window:
        mask &= (q_pos - k_pos) < window
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhst,bhtd->bhsd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)


def decode_attention_ref(q, k, v, pos, *, scale=None):
    """q: (B,H,d); k,v: (B,T,H,d); pos: (B,). Returns (o, m, l) — partial
    softmax stats so shards can LSE-combine (context-parallel decode)."""
    b, h, d = q.shape
    t = k.shape[1]
    scale = scale or 1.0 / math.sqrt(d)
    logits = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = jnp.arange(t)[None, :] <= pos[:, None]
    logits = jnp.where(mask[:, None, :], logits, -1e30)
    m = jnp.max(logits, axis=-1)
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bht,bthd->bhd", p, v.astype(jnp.float32))
    return (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype), m, l


def ssd_chunk_ref(xdt, dA, B, C):
    """One SSD chunk (intra-chunk quadratic part + chunk state).

    xdt: (Q,H,P) = x*dt; dA: (Q,H); B, C: (Q,N).
    Returns (y_diag (Q,H,P), state (H,P,N), chunk_decay (H,)).
    """
    Q, H, P = xdt.shape
    cs = jnp.cumsum(dA.astype(jnp.float32), axis=0)           # (Q,H)
    diff = cs[:, None, :] - cs[None, :, :]                    # (Q,Q,H)
    ii = jnp.arange(Q)
    L = jnp.where((ii[:, None] >= ii[None, :])[..., None],
                  jnp.exp(diff), 0.0)                         # (Q,Q,H)
    G = jnp.einsum("ln,sn->ls", C.astype(jnp.float32),
                   B.astype(jnp.float32))                     # (Q,Q)
    M = G[..., None] * L
    y = jnp.einsum("lsh,shp->lhp", M, xdt.astype(jnp.float32))
    decay_state = jnp.exp(cs[-1][None, :] - cs)               # (Q,H)
    state = jnp.einsum("sn,sh,shp->hpn", B.astype(jnp.float32),
                       decay_state, xdt.astype(jnp.float32))
    return y.astype(xdt.dtype), state, jnp.exp(cs[-1])


def quantize_int8_ref(x, block: int):
    """Blockwise symmetric int8: x (R, C) -> (q int8 (R,C), scales (R, C/block))."""
    r, c = x.shape
    xb = x.astype(jnp.float32).reshape(r, c // block, block)
    absmax = jnp.max(jnp.abs(xb), axis=-1)
    scale = jnp.maximum(absmax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(xb / scale[..., None]), -127, 127)
    return q.reshape(r, c).astype(jnp.int8), scale


def dequantize_int8_ref(q, scale, block: int, dtype=jnp.float32):
    r, c = q.shape
    xb = q.astype(jnp.float32).reshape(r, c // block, block)
    return (xb * scale[..., None]).reshape(r, c).astype(dtype)
