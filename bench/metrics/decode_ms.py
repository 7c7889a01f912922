"""Device milliseconds per execution of the decode program (jit__decode,
the engine's jitted forward_decode) in the traced window."""
from bench import trace

PROGRAM = "jit__decode"


def read(ctx):
    if ctx.trace is None:
        return None
    evs = trace.module_events(ctx.trace, PROGRAM, *ctx.trace_window)
    return sum(d for _, _, d in evs) / len(evs) / 1e6 if evs else None
