"""Pallas TPU kernels for the compressed-transport hot path (int8 codec).

The CompressedNsm quantizes gradients before they cross the pod axis; on
real hardware the quantize/dequantize sits on the critical path of every
cross-pod reduction, so it gets a kernel: blockwise symmetric int8 with one
f32 scale per (row, block). Grid walks row blocks; each program quantizes a
(rows_block, C) tile held in VMEM (256x8192 bf16 = 4 MB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import pallas_call


def _quant_kernel(x_ref, q_ref, s_ref, *, block: int):
    x = x_ref[...].astype(jnp.float32)            # (rb, C)
    rb, c = x.shape
    xb = x.reshape(rb, c // block, block)
    absmax = jnp.max(jnp.abs(xb), axis=-1)
    scale = jnp.maximum(absmax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(xb / scale[..., None]), -127, 127)
    q_ref[...] = q.reshape(rb, c).astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, o_ref, *, block: int):
    q = q_ref[...].astype(jnp.float32)
    rb, c = q.shape
    scale = s_ref[...]
    o = (q.reshape(rb, c // block, block) * scale[..., None]).reshape(rb, c)
    o_ref[...] = o.astype(o_ref.dtype)


def quantize_int8(x, *, block: int = 256, rows_block: int = 256):
    """x: (R, C) with C % block == 0 -> (q int8 (R,C), scales f32 (R, C/block))."""
    r, c = x.shape
    assert c % block == 0, (c, block)
    rb = min(rows_block, r)
    r_pad = -(-r // rb) * rb
    if r_pad != r:
        x = jnp.pad(x, ((0, r_pad - r), (0, 0)))
    q, s = pallas_call(
        functools.partial(_quant_kernel, block=block),
        grid=(r_pad // rb,),
        in_specs=[pl.BlockSpec((rb, c), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rb, c), lambda i: (i, 0)),
                   pl.BlockSpec((rb, c // block), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((r_pad, c), jnp.int8),
                   jax.ShapeDtypeStruct((r_pad, c // block), jnp.float32)],
    )(x)
    return q[:r], s[:r]


def dequantize_int8(q, scales, *, block: int = 256, rows_block: int = 256,
                    dtype=jnp.float32):
    r, c = q.shape
    rb = min(rows_block, r)
    r_pad = -(-r // rb) * rb
    if r_pad != r:
        q = jnp.pad(q, ((0, r_pad - r), (0, 0)))
        scales = jnp.pad(scales, ((0, r_pad - r), (0, 0)))
    o = pallas_call(
        functools.partial(_dequant_kernel, block=block),
        grid=(r_pad // rb,),
        in_specs=[pl.BlockSpec((rb, c), lambda i: (i, 0)),
                  pl.BlockSpec((rb, c // block), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rb, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((r_pad, c), dtype),
    )(q, scales)
    return o[:r]
