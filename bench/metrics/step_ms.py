"""Host milliseconds per ServeEngine.step, mean over the steps that
started inside the window. A step ends in the host read-back of its
tokens, so it includes the device's work."""


def read(ctx):
    lo, hi = ctx.window
    xs = [s.end - s.start for s in ctx.records.steps
          if lo <= s.start < hi and s.end]
    return sum(xs) / len(xs) * 1e3 if xs else None
