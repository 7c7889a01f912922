"""Device time of a program's ops under one of the program's name scopes
(``jax.named_scope``), for the readers of a scoped layer. Ops get their
scopes from the profile's HLO (bench/trace.py ``op_scopes``)."""
from bench import trace


def scoped_ns(ctx, scope, programs=("jit__decode",)):
    """(device ns of the ops whose name-scope path has ``scope`` as a
    component, executions of the programs) over the traced window, the
    ops counted in those programs only; None where the trace holds no op
    under ``scope``, as in a program that has no such scope."""
    if ctx.trace is None or not ctx.trace["devices"]:
        return None
    lo, hi = ctx.trace_window
    runs = sum(len(trace.module_events(ctx.trace, p, lo, hi))
               for p in programs)
    trace.op_scopes(ctx.trace)
    lines = ctx.trace["devices"]
    ops = lines[sorted(lines)[0]].get(trace.OPS_LINE, [])
    prefixes = tuple(f"jit({p[len('jit_'):]})/" for p in programs)
    ns = sum(op[2] for op in ops
             if len(op) > 3 and op[3].startswith(prefixes)
             and scope in op[3].split("/")
             and op[1] >= lo and op[1] + op[2] <= hi)
    return (ns, runs) if ns and runs else None


def scoped_ms(ctx, scope, program="jit__decode"):
    """Device ms per execution of ``program`` under ``scope``."""
    got = scoped_ns(ctx, scope, (program,))
    return None if got is None else got[0] / got[1] / 1e6
