"""Host milliseconds per RateController.tick, mean over the ticks that
started inside the window (the bench.tick span)."""


def read(ctx):
    lo, hi = ctx.window
    xs = [d for s, d in ctx.records.ticks if lo <= s < hi]
    return sum(xs) / len(xs) * 1e3 if xs else None
