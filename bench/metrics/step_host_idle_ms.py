"""Device-idle milliseconds per ``nk.engine.step`` under the step's host
work outside admission (``nk.engine.step``'s own time,
``nk.engine.prepare``, ``nk.engine.decode``, ``nk.engine.readback``,
``nk.engine.commit``, innermost): building the decode's inputs, its
dispatch, the wait on its tokens and their commit, which every
inter-token gap holds. The read-back is not read apart: see
bench/program_spans.py.
Groups: bench/program_spans.py ``GROUPS``; None in an untraced run."""
from bench import program_spans


def read(ctx):
    if ctx.trace is None:
        return None
    lo, hi = ctx.trace_window
    return program_spans.idle_ms_per_step(
        ctx.trace, lo, hi, program_spans.GROUPS["step_host_idle_ms"])
