"""jit'd public wrappers for the Pallas kernels (impl dispatch + layout).

``impl="pallas"`` runs the Mosaic kernel where the call lowers for a TPU
and the Pallas interpreter where it lowers for the CPU
(``repro.kernels.platform``); ``impl="ref"`` runs the pure-jnp oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.quant_comm import dequantize_int8 as _dq_pallas
from repro.kernels.quant_comm import quantize_int8 as _q_pallas
from repro.kernels.ssd_scan import ssd_chunk_scan as _ssd_pallas


@functools.partial(jax.jit, static_argnames=("causal", "window", "impl",
                                             "q_block", "kv_block"))
def mha_forward(q, k, v, *, causal=True, window=0, impl="pallas",
                q_block=256, kv_block=256):
    """q,k,v: (B, H, S, d) -> (B, H, S, d)."""
    b, h, s, d = q.shape
    if impl == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h, -1, d)
    vf = v.reshape(b * h, -1, d)
    o = _flash_pallas(qf, kf, vf, causal=causal, window=window,
                      q_block=q_block, kv_block=kv_block)
    return o.reshape(b, h, s, d)


@functools.partial(jax.jit, static_argnames=("impl", "kv_block"))
def decode_step_attention(q, k, v, pos, *, impl="pallas", kv_block=512):
    """q: (B,H,d); k,v: (B,T,H,d); pos: (B,). Returns (o, m, l)."""
    if impl == "ref":
        return ref.decode_attention_ref(q, k, v, pos)
    return _decode_pallas(q, k, v, pos, kv_block=kv_block)


@functools.partial(jax.jit, static_argnames=("impl", "head_block"))
def ssd_intra_chunk(xdt, dA, B, C, *, impl="pallas", head_block=8):
    """(nb, nc, Q, H, P) SSD intra-chunk. Returns (y, states, decay)."""
    if impl == "ref":
        f = jax.vmap(jax.vmap(
            lambda x, a, b_, c_: ref.ssd_chunk_ref(x, a, b_, c_)))
        return f(xdt, dA, B, C)
    return _ssd_pallas(xdt, dA, B, C, head_block=head_block)


@functools.partial(jax.jit, static_argnames=("block", "impl"))
def quantize(x, *, block=256, impl="pallas"):
    if impl == "ref":
        return ref.quantize_int8_ref(x, block)
    return _q_pallas(x, block=block)


@functools.partial(jax.jit, static_argnames=("block", "impl", "dtype"))
def dequantize(q, scales, *, block=256, impl="pallas", dtype=jnp.float32):
    if impl == "ref":
        return ref.dequantize_int8_ref(q, scales, block, dtype)
    return _dq_pallas(q, scales, block=block, dtype=dtype)
