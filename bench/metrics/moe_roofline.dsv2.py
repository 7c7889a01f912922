"""Share of the roofline reached by the expert layers: the least time
the chip could take for their work in the traced window, over their
device time (the ops under the program's ``moe`` name scope, in the
prefill and decode programs). The work is counted from the engine's
counters at the window's edges (ServeEngine.counters: routed, held
assignments and held experts touched) by the architecture's
``moe_call`` (bench/arch/mla_moe.py), over (program run, expert layer)
calls."""
from bench import flops
from bench.metrics._scoped import scoped_ns

PROGRAMS = ("jit__decode", "jit__prefill")


def read(ctx):
    a, b = ctx.counters_at_open, ctx.counters_at_close
    names = ("nk_moe_assignments_total", "nk_moe_assignments_held_total",
             "nk_moe_experts_touched_total")
    got = scoped_ns(ctx, "moe", PROGRAMS)
    if got is None or not all(k in a and k in b for k in names):
        return None
    ns, runs = got
    assigned, held, touched = (b[k] - a[k] for k in names)
    m = ctx.model
    layers = m["num_layers"] - m["dense_layer_prefix"]
    c = ctx.arch.moe_call(m, assigned / m["moe"]["top_k"], held, touched,
                          calls=runs * layers)
    ideal = flops.roofline_seconds(c["flops"], c["bytes"], ctx.peak)
    return 100.0 * ideal / (ns / 1e9)
