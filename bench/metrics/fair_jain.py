"""Jain's index over tenants of served_i / fair_i. served_i: tenant i's
billed tokens (prompt + generated) inside the window, from the ledger's
counters at the window's edges. fair_i: the weighted max-min share of the
tokens served in all, given each tenant's offered billed tokens
(prompt + requested output) due inside the window."""
from bench import stats


def read(ctx):
    lo, hi = ctx.window
    before, after = ctx.billed_at_open, ctx.billed_at_close
    served = {t: after.get(t, 0) - before.get(t, 0) for t in ctx.weights}
    demands = {t: 0.0 for t in ctx.weights}
    for r in ctx.requests:
        if lo <= r.due < hi:
            demands[r.tenant] += r.prompt_len + r.out_len
    return stats.fair_jain(served, demands, ctx.weights)
