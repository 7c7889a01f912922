"""95th percentile of due time to the return of the scheduler call that
picked the request (TenantScheduler.next_request), over the light
tenants' requests due inside the window (the mix's latency tenants); one
not picked by the close counts at its age. Above the knee this tail swings
from run to run, so it is recorded here and not judged."""
from bench import stats


def read(ctx):
    lo, hi = ctx.window
    return stats.percentile(stats.queue_wait_samples(
        ctx.requests, lo, hi, ctx.latency_tenants), 95)
