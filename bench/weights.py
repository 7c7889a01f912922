"""Weights drawn from the seed, on the device, in one jitted call.

The benchmark makes the weights, in the dtype they are served in, and
hands the same arrays to the program under test and to the plain
reference. Their layout is the architecture's (``bench/arch/<arch>.py``
``layout``: each tensor's path in the weight tree, its shape, and the
standard deviation of its normal draw, or None for ones); the
architecture's ``program_params`` rearranges the tree into the program's
without copying.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (beyond 32 bits too)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def make_weights(layout: List, dtype: str, seed: int) -> Dict:
    """The weight tree of ``layout``: one key of the seed's split per
    tensor, in the layout's order."""
    dtype = jnp.dtype(dtype)

    def make(key):
        keys = jax.random.split(key, len(layout))
        out: Dict = {}
        for k, (path, shape, std) in zip(keys, layout):
            node = out
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = jnp.ones(shape, dtype) if std is None else \
                (jax.random.normal(k, shape, jnp.float32) * std
                 ).astype(dtype)
        return out

    return jax.jit(make)(seed_key(seed))
