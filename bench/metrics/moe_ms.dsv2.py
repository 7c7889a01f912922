"""Device milliseconds per decode program under the program's ``moe``
name scope (repro.models.moe.apply_moe: routing, the held experts and the
shared experts, every expert layer), in the decode-heavy cell."""
from bench.metrics._scoped import scoped_ms


def read(ctx):
    return scoped_ms(ctx, "moe")
