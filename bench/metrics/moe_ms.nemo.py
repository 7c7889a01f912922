"""Device milliseconds per decode program under the program's ``moe``
name scope (repro.models.moe.apply_moe: sigmoid routing with the
correction bias, the held experts and the shared expert, every expert
block), in the Nemotron-H decode-heavy cell."""
from bench.metrics._scoped import scoped_ms


def read(ctx):
    return scoped_ms(ctx, "moe")
