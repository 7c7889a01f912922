"""Share of the roofline reached by the Mamba-2 blocks in the decode
program: the least time the chip could take for their work, summed over
the traced window's decodes (the architecture's ``ssm_call`` of each
decode's live slots, bench/arch/mamba_moe.py: the mixers' weights once,
each live slot's f32 state and conv tails read and written), over the
device time of the decode program's ops under the ``ssm`` name scope."""
from bench import flops, trace
from bench.metrics._scoped import scoped_ns

PROGRAM = "jit__decode"


def read(ctx):
    got = scoped_ns(ctx, "ssm", (PROGRAM,))
    if got is None:
        return None
    ideal = 0.0
    for _, s, _ in trace.module_events(ctx.trace, PROGRAM,
                                       *ctx.trace_window):
        k = ctx.step_of(s)
        if k is not None and ctx.steps[k].decode_positions:
            c = ctx.arch.ssm_call(ctx.model,
                                  len(ctx.steps[k].decode_positions))
            ideal += flops.roofline_seconds(c["flops"], c["bytes"], ctx.peak)
    return 100.0 * ideal / (got[0] / 1e9)
