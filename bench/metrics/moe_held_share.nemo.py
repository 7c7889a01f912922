"""Share of the window's routed assignments that land on the experts this
chip holds, from the engine's counters at the window's edges
(ServeEngine.counters: nk_moe_assignments_held_total over
nk_moe_assignments_total), in the Nemotron-H decode-heavy cell. Under
uniform routing it is the held experts' share of the router (16 / 128 =
12.5%); the correction bias tilts the choice, and more means this chip
gets more than its share of the expert work."""


def read(ctx):
    a, b = ctx.counters_at_open, ctx.counters_at_close
    total, held = "nk_moe_assignments_total", "nk_moe_assignments_held_total"
    if not all(k in a and k in b for k in (total, held)):
        return None
    n = b[total] - a[total]
    return 100.0 * (b[held] - a[held]) / n if n else None
