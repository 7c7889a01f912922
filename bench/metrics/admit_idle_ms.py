"""Device-idle milliseconds per ``nk.engine.step`` under the program's
admission regions (``nk.engine.admit``'s own time, ``nk.scheduler.pick``,
``nk.engine.prefill``, ``nk.engine.install``, ``nk.engine.first_token``,
innermost): host work between a request's pick and its first token,
which time to first token waits for.
Groups: bench/program_spans.py ``GROUPS``; None in an untraced run."""
from bench import program_spans


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace_window
    return program_spans.idle_ms_per_step(
        ctx.trace, lo, hi, program_spans.GROUPS["admit_idle_ms"])
