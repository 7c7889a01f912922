"""80th percentile of due time to the return of the scheduler call
(TenantScheduler.next_request) that picked the request, over the same
requests as ttft_p80_s; one not picked by the close counts at its age."""
from bench import stats


def read(ctx):
    lo, hi = ctx.window
    return stats.percentile(stats.queue_wait_samples(
        ctx.requests, lo, hi, ctx.latency_tenants), 80)
