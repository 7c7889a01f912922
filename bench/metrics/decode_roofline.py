"""Share of the roofline reached by the decode program: the sum over its
executions of max(ops / peak FLOP/s, bytes / peak HBM bytes/s), over the
sum of their device time. Ops and bytes are what the algorithm needs
(the architecture's ``decode_call``, bench/arch/<arch>.py; for the dense
decoder every weight read once, the K/V cache read only up to each active
slot's position, one token's K/V written per active slot)."""
from bench import flops, trace

PROGRAM = "jit__decode"


def read(ctx):
    if ctx.trace is None:
        return None
    ideal = dev = 0.0
    for _, s, d in trace.module_events(ctx.trace, PROGRAM,
                                       *ctx.trace_window):
        k = ctx.step_of(s)
        if k is None or not ctx.steps[k].decode_positions:
            continue
        c = ctx.arch.decode_call(ctx.model, ctx.steps[k].decode_positions)
        ideal += flops.roofline_seconds(c["flops"], c["bytes"], ctx.peak)
        dev += d / 1e9
    return 100.0 * ideal / dev if dev else None
