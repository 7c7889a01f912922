"""The readers of the flash-decode kernel's per-layer metrics through a
``run.Ctx``: the share of cache blocks its decode read, from the engine's
counters, and the device ms per decode under the ``attn`` scope, from a
hand-made trace. Each reads nothing where the program has no such counter
or scope."""
import pytest

from bench import run


@pytest.mark.parametrize("metric", ["decode_kv_read_pct",
                                    "decode_kv_read_pct.noisy"])
def test_kv_read_share_reads_the_window_of_the_counters(metric):
    """The share of cache blocks the decode kernel read is the window's
    delta of blocks read over blocks whole; nothing where the engine has
    no such counters (a program without the kernel) or its kernel ran on
    no layer."""
    names = ("nk_decode_kv_blocks_read_total", "nk_decode_kv_blocks_total")
    ctx = run.Ctx(counters_at_open=dict(zip(names, (100.0, 400.0))),
                  counters_at_close=dict(zip(names, (124.0, 592.0))))
    assert run.reader(metric)(ctx) == pytest.approx(12.5)
    ctx.counters_at_close = dict(zip(names, (100.0, 400.0)))
    assert run.reader(metric)(ctx) is None
    ctx = run.Ctx(counters_at_open={"nk_decode_cache_inplace_segments": 1.0},
                  counters_at_close={"nk_decode_cache_inplace_segments": 1.0})
    assert run.reader(metric)(ctx) is None


@pytest.mark.parametrize("metric", ["attn_ms", "attn_ms.noisy"])
def test_attention_ms_reads_the_attn_scope_of_the_decode(metric):
    """Device ms per decode of the ops under ``attn`` in the decode program
    only; nothing where no op has that scope, as in a program without it,
    or in an untraced run."""
    ops = [["flash_decode.7", 10.0, 3e5,
            "jit(_decode)/while/body/closed_call/checkpoint/attn/cond/"
            "branch_0_fun/pallas_call"],
           ["fusion.2", 4e5, 1e5, "jit(_decode)/while/body/attn/dot"],
           ["fusion.3", 6e5, 2e5, "jit(_decode)/while/body/mlp/dot"],
           ["fusion.4", 9e5, 5e4, "jit(_prefill)/while/body/attn/dot"]]
    ctx = run.Ctx(trace={"devices": {"/device:TPU:0": {
        "XLA Modules": [["jit__decode", 0.0, 8e5],
                        ["jit__decode", 8e5, 1e5]],
        "XLA Ops": ops}}, "host": [], "program": []},
        trace_window=(0.0, 1e9))
    assert run.reader(metric)(ctx) == pytest.approx(0.2)
    ctx.trace["devices"]["/device:TPU:0"]["XLA Ops"] = ops[2:]
    assert run.reader(metric)(ctx) is None
    ctx.trace = None
    assert run.reader(metric)(ctx) is None
