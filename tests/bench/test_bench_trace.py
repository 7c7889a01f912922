"""The trace reduction (bench/trace.py) on a hand-made record and on a
small trace recorded on a TPU v5e (fixtures/trace_v5e.json.gz)."""
import gzip
import json
from pathlib import Path

import pytest

from bench import trace

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "trace_v5e.json.gz"


def hand_made():
    ms = 1_000_000
    ops = [["fusion.1", 0 * ms, 4 * ms], ["fusion.2", 3 * ms, 3 * ms],
           ["fusion.1", 10 * ms, 2 * ms], ["copy", 15 * ms, 10 * ms]]
    mods = [["jit__decode(7)", 0 * ms, 6 * ms],
            ["jit__prefill(3)", 10 * ms, 2 * ms],
            ["jit__decode(7)", 15 * ms, 10 * ms]]
    host = [["bench.window", 0, 20 * ms, {}],
            ["bench.step", 0, 8 * ms, {"step": 4}],
            ["bench.readback", 5 * ms, 3 * ms, {}],
            ["bench.step", 9 * ms, 11 * ms, {"step": 5}],
            ["bench.tick", 12 * ms, 2 * ms, {}]]
    return {"devices": {"/device:TPU:0": {"XLA Ops": ops,
                                          "XLA Modules": mods}},
            "host": host}


def test_busy_idle_and_spans():
    rec = hand_made()
    ms = 1e6
    lo, hi = trace.host_span(rec, "bench.window")
    assert (lo, hi) == (0, 20 * ms)
    # ops cover [0, 6) [10, 12) [15, 20) inside the window: 13 ms busy
    assert trace.busy_seconds(rec, lo, hi) == pytest.approx(0.013)
    idle = dict(trace.idle_by_span(rec, lo, hi))
    # idle [6, 10) and [12, 15): [6, 8) in readback (innermost), [8, 9)
    # outside every span, [9, 10) in step 5, [12, 14) in tick, [14, 15)
    # in step 5 again
    assert idle == pytest.approx({"bench.readback": 0.002,
                                  "host:outside-spans": 0.001,
                                  "bench.tick": 0.002,
                                  "bench.step": 0.002})
    ops = dict(trace.top_ops(rec, lo, hi))
    assert trace.op_name("%fusion.3 = bf16[2]{0} fusion(...)") == "fusion.3"
    assert ops == pytest.approx({"fusion.1": 0.006, "fusion.2": 0.003})
    dec = trace.module_events(rec, "jit__decode", lo, hi)
    assert [e[1] for e in dec] == [0]          # the second ends past hi
    spans = trace.step_spans(rec)
    assert trace.step_at(spans, 10 * ms) == 5
    assert trace.step_at(spans, 8.5 * ms) is None


def test_union_merges_and_clips():
    assert trace.union([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 10) == \
        [(1, 4), (5, 10)]
    assert trace.gaps([(1, 4), (5, 10)], 0, 12) == [(0, 1), (4, 5), (10, 12)]


def test_recorded_v5e_trace():
    """Three decode steps of internlm2-1.8b.chat, recorded on one TPU v5e."""
    with gzip.open(FIXTURE, "rt") as f:
        rec = json.load(f)
    lo, hi = trace.host_span(rec, "bench.window")
    busy = trace.busy_seconds(rec, lo, hi)
    assert 0 < busy < (hi - lo) / 1e9
    dec = trace.module_events(rec, "jit__decode", lo, hi)
    spans = trace.step_spans(rec)
    assert dec and all(trace.step_at(spans, s) is not None
                       for _, s, _ in dec)
    gaps = trace.idle_by_span(rec, lo, hi)
    assert sum(v for _, v in gaps) == pytest.approx(
        (hi - lo) / 1e9 - busy, rel=1e-6)
    # the decode loop runs inside its program; loops are not listed
    assert all(len(d) == 3 and len(d[0]) < 100 for d in dec)
    names = [n for n, _ in trace.top_ops(rec, lo, hi)]
    assert names and not any(n.startswith("while") for n in names)
    assert all(" = " not in n for n in names)
