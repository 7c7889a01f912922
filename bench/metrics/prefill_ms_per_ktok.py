"""Device milliseconds of the prefill programs (jit__prefill) per 1000
prompt tokens prefilled, over the steps of the traced window."""
from bench import trace

PROGRAM = "jit__prefill"


def read(ctx):
    if ctx.trace is None:
        return None
    evs = trace.module_events(ctx.trace, PROGRAM, *ctx.trace_window)
    dev, steps = 0.0, set()
    for _, s, d in evs:
        k = ctx.step_of(s)
        if k is not None:
            dev += d
            steps.add(k)
    toks = sum(sum(ctx.steps[k].prefill_lens) for k in steps)
    return dev / 1e6 / (toks / 1e3) if toks else None
