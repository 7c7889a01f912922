"""Pallas calls that interpret on the CPU and compile on the TPU.

The choice is made per lowering platform with ``lax.platform_dependent``,
not from the process's default backend: one jitted function runs the
Pallas interpreter when it lowers for the CPU and the Mosaic kernel when
it lowers for a TPU, including a TPU that is only described (the compile
tests build programs for a v5e from a CPU-only process). No kernel falls
back to the interpreter on a TPU.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, **kw):
    """``pl.pallas_call(kernel, **kw)``, interpreted only where the call
    lowers for the CPU."""
    compiled = pl.pallas_call(kernel, **kw)
    interpreted = pl.pallas_call(kernel, interpret=True, **kw)

    def call(*args):
        return jax.lax.platform_dependent(*args, cpu=interpreted,
                                          default=compiled)
    return call
