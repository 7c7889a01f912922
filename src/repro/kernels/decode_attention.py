"""Pallas TPU decode attention: one query token vs a (sharded) KV cache.

Grid walks (batch, head, kv blocks); each program holds one head's query
row (1, d) in VMEM while that head's cache blocks (kvb, d) stream through,
sliced straight out of the (B, T, H*d) cache view, so no head transpose
is materialized. The decode positions ride in SMEM as a scalar-prefetch
operand. Emits per-shard partial stats (o, m, l) so the context-parallel
decode path can LSE-combine across the model axis (the ``psum`` the serve
engine's distributed decode performs) — the kernel is the *local* half of
distributed flash-decode.

VMEM working set per program: q (1,d) + k/v blocks (kvb, d) + acc (1,d) —
with d=128, kvb=512 in bf16: ~0.3 MB. On a TPU, d must be a multiple of
128 (the lane width) unless the cache holds a single head.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call

NEG_INF = -2.0e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   m_sc, l_sc, acc_sc, *, scale: float, kv_block: int,
                   kv_len: int):
    jk = pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    pos = pos_ref[pl.program_id(0)]
    k_lo = jk * kv_block
    s = jax.lax.dot_general(
        q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # (1, kvb)
    t_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = (t_pos <= pos) & (t_pos < kv_len)
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_sc[...]                                   # (1, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_sc[...] = acc_sc[...] * corr + jax.lax.dot(
        p.astype(v_ref.dtype), v_ref[...],
        preferred_element_type=jnp.float32)
    m_sc[...] = m_new

    @pl.when(jk == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)
                      ).astype(o_ref.dtype)
        m_ref[...] = m_sc[...]
        l_ref[...] = l_sc[...]


def decode_attention(q, k, v, pos, *, scale=None, kv_block=512):
    """q: (B,H,d); k,v: (B,T,H,d) (kv already GQA-expanded or H==KV);
    pos: (B,). Returns (o (B,H,d), m (B,H), l (B,H))."""
    b, h, d = q.shape
    t = k.shape[1]
    scale = scale or 1.0 / math.sqrt(d)
    kv_block = min(kv_block, t)
    t_pad = -(-t // kv_block) * kv_block
    if t_pad != t:
        k = jnp.pad(k, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, t_pad - t), (0, 0), (0, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, t_pad // kv_block),
        in_specs=[
            pl.BlockSpec((None, None, 1, d), lambda i, n, j, p: (i, n, 0, 0)),
            pl.BlockSpec((None, kv_block, d), lambda i, n, j, p: (i, j, n)),
            pl.BlockSpec((None, kv_block, d), lambda i, n, j, p: (i, j, n)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, 1, d), lambda i, n, j, p: (i, n, 0, 0)),
            pl.BlockSpec((None, None, 1, 1), lambda i, n, j, p: (i, n, 0, 0)),
            pl.BlockSpec((None, None, 1, 1), lambda i, n, j, p: (i, n, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
    )
    o, m, l = pallas_call(
        functools.partial(_decode_kernel, scale=scale, kv_block=kv_block,
                          kv_len=t),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, 1, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, h, 1, 1), jnp.float32),
        ],
    )(pos.astype(jnp.int32), q.reshape(b, h, 1, d),
      k.reshape(b, t_pad, h * d), v.reshape(b, t_pad, h * d))
    return o[:, :, 0], m[:, :, 0, 0], l[:, :, 0, 0]
