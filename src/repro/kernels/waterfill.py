"""Pallas kernel for the weighted max-min water-fill inner loop.

The fused control tick allocates capacity across the whole tenant
population every interval; at fleet scale (100k+ tenants) the water-fill
is the only super-linear step if done by sorting. This kernel does it in
O(iters x n): a fixed-iteration bisection on the common water level L —
S(L) = sum_t w_t * min(demand_t / w_t, L) is concave nondecreasing in L,
so the level where S(L) == capacity brackets in [0, capacity / min_w]
and halves every iteration. No sort, no data-dependent control flow;
the whole population is one (rows, 128) VMEM tile reduced per iteration,
and the two scalars (capacity in, water level out) live in SMEM.

Semantics match ``repro.kernels.ref.water_fill_ref`` (and the scalar
``max_min_fair``): slots with demand <= 0 or weight <= 0 are parked at 0,
``inf`` demand = greedy, satisfied tenants (ratio <= level) take their
demand exactly, the rest sit at weight x level.

The TPU kernel is 32-bit only: Mosaic converts no 64-bit scalar, so a
float64 call on a TPU raises instead of running at another precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import pallas_call

_LANES = 128


def _waterfill_kernel(d_ref, w_ref, c_ref, a_ref, l_ref, *, iters: int):
    d = d_ref[...]                                   # (rows, 128)
    w = w_ref[...]
    cap = c_ref[0]
    active = (d > 0) & (w > 0)
    w = jnp.where(active, w, 0.0)
    r = jnp.where(active, d / jnp.where(active, w, 1.0), 0.0)
    min_w = jnp.min(jnp.where(active, w, jnp.inf))
    # cap / min_w upper-bounds the true level: any tenant with ratio
    # above it would alone absorb the whole capacity
    hi0 = jnp.where(jnp.isfinite(min_w),
                    cap / jnp.maximum(min_w, jnp.asarray(1e-30, d.dtype)),
                    jnp.asarray(0.0, d.dtype))

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        filled = jnp.sum(w * jnp.minimum(r, mid))
        over = filled > cap
        return jnp.where(over, lo, mid), jnp.where(over, mid, hi)

    _, lvl = jax.lax.fori_loop(0, iters, body, (jnp.zeros_like(hi0), hi0))
    a_ref[...] = jnp.where(active,
                           jnp.where(r <= lvl, d, w * lvl), 0.0)
    l_ref[0] = lvl


def water_fill_pallas(demands, weights, capacity, *, iters: int = 48,
                      rows_block: int = 8):
    """demands, weights: (n,) -> alloc (n,). Pads n up to a multiple of
    ``rows_block * 128`` (padding parks as weight-0 slots)."""
    d = jnp.asarray(demands)
    w = jnp.asarray(weights, dtype=d.dtype)
    if d.dtype.itemsize > 4 and jax.default_backend() == "tpu":
        raise TypeError(
            f"water_fill_pallas: the TPU kernel is 32-bit only (Mosaic "
            f"converts no 64-bit scalar); got {d.dtype}. Call it outside "
            f"jax.enable_x64 or use impl='ref'.")
    n = d.shape[0]
    tile = rows_block * _LANES
    n_pad = max(-(-n // tile) * tile, tile)
    if n_pad != n:
        d = jnp.pad(d, (0, n_pad - n))
        w = jnp.pad(w, (0, n_pad - n))
    rows = n_pad // _LANES
    cap = jnp.full((1,), capacity, dtype=d.dtype)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    alloc, _ = pallas_call(
        functools.partial(_waterfill_kernel, iters=iters),
        in_specs=[vmem, vmem, smem],
        out_specs=[vmem, smem],
        out_shape=[jax.ShapeDtypeStruct((rows, _LANES), d.dtype),
                   jax.ShapeDtypeStruct((1,), d.dtype)],
    )(d.reshape(rows, _LANES), w.reshape(rows, _LANES), cap)
    return alloc.reshape(n_pad)[:n]
