"""Device milliseconds per decode program under the program's ``mla``
name scope (repro.models.attention.mla_attention: projections, the
latent cache write and the absorbed attention over it, every layer), in
the decode-heavy cell."""
from bench.metrics._scoped import scoped_ms


def read(ctx):
    return scoped_ms(ctx, "mla")
