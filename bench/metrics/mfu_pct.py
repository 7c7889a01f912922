"""The whole step's share of the chip's peak bf16 FLOP/s: the operations
that every prompt and generated token processed in the traced window
needs (the architecture's ``prefill_flops`` and ``decode_call``,
bench/arch/<arch>.py: 2 x parameters multiplied, plus attention over its
context), over the traced window's seconds x peak."""


def read(ctx):
    if ctx.trace is None or not ctx.trace["devices"]:
        return None
    m, total = ctx.model, 0.0
    for st in ctx.steps_in_trace():
        total += sum(ctx.arch.prefill_flops(m, n) for n in st.prefill_lens)
        total += ctx.arch.decode_call(m, st.decode_positions)["flops"]
    lo, hi = ctx.trace_window
    return 100.0 * total / ((hi - lo) / 1e9 * ctx.peak["bf16_flops_per_s"])
