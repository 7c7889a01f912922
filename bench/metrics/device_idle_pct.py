"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals / window), averaged over the chips."""
from bench import trace


def read(ctx):
    if ctx.trace is None or not ctx.trace["devices"]:
        return None
    lo, hi = ctx.trace_window
    return 100.0 * (1.0 - trace.busy_seconds(ctx.trace, lo, hi)
                    / ((hi - lo) / 1e9))
