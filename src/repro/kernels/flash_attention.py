"""Pallas TPU flash attention (forward): VMEM-tiled online softmax.

TPU-native layout (B*H, S, d): the grid walks (batch*head, q blocks); each
program streams kv blocks of its row through VMEM with (m, l, acc) carried
in VMEM scratch. Causal/window blocks that are fully masked are skipped
with ``pl.when`` (no MXU cycles spent). Block shapes are MXU-aligned
(multiples of 128 on the lane dim; q/kv blocks of 128-512 rows keep the
working set q + k + v + acc well under ~16 MB VMEM:
    512x128 q (bf16)   128 KB
    512x128 k,v (bf16) 256 KB
    512x512 s (f32)      1 MB
    512x128 acc (f32)  256 KB
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
                  causal: bool, window: int, scale: float, kv_block: int,
                  kv_len: int):
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    q_block = q_ref.shape[0]

    @pl.when(jk == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q_lo = iq * q_block
    k_lo = jk * kv_block
    # static-shape test for whether this (q,kv) block pair can contribute
    def compute():
        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 0)
        k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (q_block, kv_block), 1)
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        mask = k_pos < kv_len
        if causal:
            mask &= q_pos >= k_pos
        if window:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1)
        acc_sc[...] = acc_sc[...] * corr[:, None] + jax.lax.dot(
            p.astype(v_ref.dtype), v_ref[...],
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    # skip fully-masked block pairs (saves the MXU work the triangular /
    # banded structure allows)
    live = True
    if causal:
        live = q_lo + q_block - 1 >= k_lo
    if window:
        live = jnp.logical_and(live, k_lo + kv_block - 1 > q_lo - window) \
            if not isinstance(live, bool) else \
            (k_lo + kv_block - 1 > q_lo - window)
    if isinstance(live, bool):
        if live:
            compute()
    else:
        pl.when(live)(compute)

    @pl.when(jk == pl.num_programs(2) - 1)
    def _finish():
        o_ref[...] = (acc_sc[...] /
                      jnp.maximum(l_sc[...], 1e-30)[:, None]
                      ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=0, scale=None,
                    q_block=256, kv_block=256):
    """q: (BH, S, d); k, v: (BH, T, d). Returns (BH, S, d)."""
    bh, s, d = q.shape
    t = k.shape[1]
    scale = scale or 1.0 / math.sqrt(d)
    q_block = min(q_block, s)
    kv_block = min(kv_block, t)
    s_pad = -(-s // q_block) * q_block
    t_pad = -(-t // kv_block) * kv_block
    if s_pad != s:
        q = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0)))
    if t_pad != t:
        k = jnp.pad(k, ((0, 0), (0, t_pad - t), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, t_pad - t), (0, 0)))
    grid = (bh, s_pad // q_block, t_pad // kv_block)
    out = pallas_call(
        functools.partial(_flash_kernel, causal=causal, window=window,
                          scale=scale, kv_block=kv_block, kv_len=t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, q_block, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, kv_block, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, kv_block, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, q_block, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block,), jnp.float32),
            pltpu.VMEM((q_block, d), jnp.float32),
        ],
    )(q, k, v)
    return out[:, :s]
