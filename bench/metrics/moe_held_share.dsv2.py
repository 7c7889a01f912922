"""Share of the window's routed assignments that land on the experts this
chip holds, from the engine's counters at the window's edges
(ServeEngine.counters: nk_moe_assignments_held_total over
nk_moe_assignments_total). Under uniform routing it is the held experts'
share of the router (10 / 160 = 6.25%); more means the routing sends this
chip more than its share, and the expert layer's work grows with it."""


def read(ctx):
    a, b = ctx.counters_at_open, ctx.counters_at_close
    total, held = "nk_moe_assignments_total", "nk_moe_assignments_held_total"
    if not all(k in a and k in b for k in (total, held)):
        return None
    n = b[total] - a[total]
    return 100.0 * (b[held] - a[held]) / n if n else None
