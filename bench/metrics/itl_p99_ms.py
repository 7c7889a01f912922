"""99th percentile of the gap between consecutive delivered tokens of one
request, over every gap that ends inside the window, all tenants (host
clock). At 0.8 x the knee about one step in twenty admits a request,
so the 95th percentile sits on the edge between plain decode steps and
steps that also prefill, and jumps between them from run to run; the
99th lies among the steps that admit."""
from bench import stats


def read(ctx):
    lo, hi = ctx.window
    p = stats.percentile(stats.itl_samples(ctx.requests, lo, hi), 99)
    return None if p is None else p * 1e3
