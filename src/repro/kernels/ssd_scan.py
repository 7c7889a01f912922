"""Pallas TPU kernel for the Mamba-2 SSD intra-chunk compute.

The SSD hot spot is the quadratic intra-chunk part: per (batch, chunk), the
masked decay matrix L = exp(segsum(dA)), the Gram matrix G = C B^T, the
chunk output Y = (G .* L) X and the outgoing chunk state. The inter-chunk
recurrence is O(chunks) and stays in jnp (repro/models/ssm.py).

Grid: (batch*chunks, head blocks). Per-program VMEM (Q=256, hb=8, P=64,
N=128, f32): L (Q,Q,hb) 2 MB + x (Q,hb,P) 0.5 MB + state (hb,P,N) 0.25 MB —
comfortably inside VMEM with MXU-aligned last dims.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import pallas_call


def _ssd_chunk_kernel(xdt_ref, dA_ref, b_ref, c_ref, y_ref, st_ref, dec_ref):
    xdt = xdt_ref[...].astype(jnp.float32)      # (Q, hb, P)
    dA = dA_ref[...].astype(jnp.float32)        # (Q, hb)
    B = b_ref[...].astype(jnp.float32)          # (Q, N)
    C = c_ref[...].astype(jnp.float32)          # (Q, N)
    Q = xdt.shape[0]

    cs = jnp.cumsum(dA, axis=0)                                  # (Q, hb)
    diff = cs[:, None, :] - cs[None, :, :]                       # (Q, Q, hb)
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where((ii >= jj)[..., None], jnp.exp(diff), 0.0)     # (Q, Q, hb)
    G = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (Q, Q)
    M = G[..., None] * L                                         # (Q, Q, hb)
    y = jnp.einsum("lsh,shp->lhp", M, xdt)                       # (Q, hb, P)

    decay_state = jnp.exp(cs[-1][None, :] - cs)                  # (Q, hb)
    st = jnp.einsum("sn,sh,shp->hpn", B, decay_state, xdt)       # (hb, P, N)

    y_ref[...] = y.astype(y_ref.dtype)
    st_ref[...] = st
    dec_ref[...] = jnp.exp(cs[-1])


def ssd_chunk_scan(xdt, dA, B, C, *, head_block=8):
    """Intra-chunk SSD over all chunks.

    xdt: (nb, nc, Q, H, P); dA: (nb, nc, Q, H); B, C: (nb, nc, Q, N).
    Returns (y_diag (nb,nc,Q,H,P), states (nb,nc,H,P,N), decay (nb,nc,H)).
    """
    nb, nc, Q, H, P = xdt.shape
    N = B.shape[-1]
    hb = min(head_block, H)
    assert H % hb == 0, (H, hb)
    grid = (nb * nc, H // hb)
    xdt_f = xdt.reshape(nb * nc, Q, H, P)
    dA_f = dA.reshape(nb * nc, Q, H)
    B_f = B.reshape(nb * nc, Q, N)
    C_f = C.reshape(nb * nc, Q, N)
    y, st, dec = pallas_call(
        _ssd_chunk_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, Q, hb, P), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((None, Q, hb), lambda i, j: (i, 0, j)),
            pl.BlockSpec((None, Q, N), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, Q, N), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, Q, hb, P), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((None, hb, P, N), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((None, hb), lambda i, j: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb * nc, Q, H, P), xdt.dtype),
            jax.ShapeDtypeStruct((nb * nc, H, P, N), jnp.float32),
            jax.ShapeDtypeStruct((nb * nc, H), jnp.float32),
        ],
    )(xdt_f, dA_f, B_f, C_f)
    return (y.reshape(nb, nc, Q, H, P), st.reshape(nb, nc, H, P, N),
            dec.reshape(nb, nc, H))
