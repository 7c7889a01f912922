"""Device milliseconds per decode program under the program's ``ssm``
name scope (repro.models.ssm.ssm_block: in-projections, the conv over
each slot's tails, the f32 state's update and read-out, the gated norm
and out-projection, every Mamba-2 block), in the Nemotron-H decode-heavy
cell."""
from bench.metrics._scoped import scoped_ms


def read(ctx):
    return scoped_ms(ctx, "ssm")
