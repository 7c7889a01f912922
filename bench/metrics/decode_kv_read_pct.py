"""Share of the K/V cache blocks that the decode's flash-decode kernel
read in the window, of those a read of every position would have taken
(ServeEngine.counters: the window's deltas of
nk_decode_kv_blocks_read_total over nk_decode_kv_blocks_total, summed from
the positions each step sent, over the layers that run the kernel). None
where the engine has no such counters or no layer runs the kernel."""

READ, WHOLE = "nk_decode_kv_blocks_read_total", "nk_decode_kv_blocks_total"


def read(ctx):
    a, b = ctx.counters_at_open, ctx.counters_at_close
    if not all(k in a and k in b for k in (READ, WHOLE)):
        return None
    whole = b[WHOLE] - a[WHOLE]
    return 100.0 * (b[READ] - a[READ]) / whole if whole else None
