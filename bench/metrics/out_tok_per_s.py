"""Generated tokens delivered inside the window, all tenants, over the
window's seconds (host clock)."""
from bench import stats


def read(ctx):
    lo, hi = ctx.window
    return stats.tokens_in_window(ctx.requests, lo, hi) / (hi - lo)
