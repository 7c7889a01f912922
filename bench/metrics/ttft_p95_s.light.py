"""95th percentile of the light tenants' time to first token: due time to
the return of the step that delivered the first token, over the mix's
latency tenants' requests due inside the window; one with no first token
at the close counts at its age. Above the knee this tail swings from run
to run, so it is recorded here and not judged."""
from bench import stats


def read(ctx):
    lo, hi = ctx.window
    return stats.percentile(stats.ttft_samples(
        ctx.requests, lo, hi, ctx.latency_tenants), 95)
