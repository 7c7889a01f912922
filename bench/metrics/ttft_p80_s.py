"""80th percentile of time to first token over requests due inside the
window from the mix's latency tenants: the return of the step that
delivered the first token minus the request's due time on the open-loop
schedule. A request with no first token at the close counts at its age
then (host clock). At 0.8 x the knee a 51 s window holds some 70
requests: the 80th percentile is the highest with ten or more beyond it."""
from bench import stats


def read(ctx):
    lo, hi = ctx.window
    return stats.percentile(stats.ttft_samples(
        ctx.requests, lo, hi, ctx.latency_tenants), 80)
