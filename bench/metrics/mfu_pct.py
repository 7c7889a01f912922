"""The whole step's share of the chip's peak bf16 FLOP/s: the operations
that every prompt and generated token processed in the traced window
needs (2 x parameters multiplied, plus attention over its context;
bench/flops.py), over the traced window's seconds x peak."""
from bench import flops


def read(ctx):
    if ctx.trace is None or not ctx.trace["devices"]:
        return None
    m, total = ctx.model, 0.0
    for st in ctx.steps_in_trace():
        total += sum(flops.prefill_flops(m, n) for n in st.prefill_lens)
        total += flops.decode_call(m, st.decode_positions)["flops"]
    lo, hi = ctx.trace_window
    return 100.0 * total / ((hi - lo) / 1e9 * ctx.peak["bf16_flops_per_s"])
