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


def test_an_op_takes_its_scope_from_the_program_that_holds_it():
    ms = 1_000_000
    rec = hand_made()
    ops = rec["devices"]["/device:TPU:0"]["XLA Ops"]
    ops[0][0] = "%fusion.1 = bf16[2]{0} fusion(bf16[2]{0} %p), kind=kLoop"
    mods = rec["devices"]["/device:TPU:0"]["XLA Modules"]
    scopes = {"jit__decode(7)": {"fusion.1": "jit(_decode)/while/body/mlp",
                                 "copy": "jit(_decode)/copy"},
              "jit__prefill(3)": {"fusion.2": "jit(_prefill)/attn"}}
    trace.add_scopes(ops, mods, scopes)
    # [0, 6) decode, [10, 12) prefill, [15, 25) decode
    assert [op[3:] for op in ops] == [["jit(_decode)/while/body/mlp"], [],
                                      [], ["jit(_decode)/copy"]]
    assert ops[1][1] == 3 * ms           # fusion.2 runs under decode
    assert len(ops[2]) == 3              # fusion.1 is not prefill's


@pytest.fixture(scope="module")
def probe_profile(tmp_path_factory):
    """A profile of one jitted call under a named scope, recorded on the
    CPU (whose ops are not on a device plane): its directory and bytes."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def probe(x):
        with jax.named_scope("bench_probe"):
            return jnp.tanh(x @ x).sum()
    x = jnp.ones((8, 8))
    probe(x).block_until_ready()
    d = tmp_path_factory.mktemp("probe")
    jax.profiler.start_trace(str(d))
    probe(x).block_until_ready()
    jax.profiler.stop_trace()
    path, = d.glob("**/*.xplane.pb")
    return d, path.read_bytes()


def test_hlo_scopes_from_a_profile(probe_profile):
    """The programs' HLO that a profile keeps names each instruction's
    scope."""
    d, raw = probe_profile
    scopes = trace.plane_scopes(trace.metadata_plane(raw))
    name, = [m for m in scopes if m.startswith("jit_probe(")]
    assert "jit(probe)/bench_probe/tanh" in scopes[name].values()
    assert trace.metadata_plane(b"") == b"" and trace.plane_scopes(b"") == {}
    rec = trace.load(str(d))
    assert rec["devices"] == {} and rec["host"] == rec["program"] == []


def test_op_scopes_are_joined_on_demand(probe_profile):
    """``load`` keeps the profile's HLO and joins nothing; ``op_scopes``
    gives the ops their scopes once, and drops the HLO."""
    d, raw = probe_profile
    kept = trace.load(str(d))["hlo"]
    assert kept and kept == trace.metadata_plane(raw)
    scopes = trace.plane_scopes(kept)
    program, = [m for m in scopes if m.startswith("jit_probe(")]
    inst, path = next((i, s) for i, s in scopes[program].items()
                      if s.endswith("/tanh"))
    rec = hand_made()
    dev = rec["devices"]["/device:TPU:0"]
    for mod in dev["XLA Modules"]:
        mod[0] = program
    for k, op in enumerate(dev["XLA Ops"]):
        op[0] = f"no_such_op.{k}"
    dev["XLA Ops"][0][0] = f"%{inst} = f32[8,8]{{1,0}} tanh(f32[8,8] %p)"
    rec["hlo"] = kept
    assert trace.op_scopes(rec) is rec and "hlo" not in rec
    assert [op[3:] for op in dev["XLA Ops"]] == [[path], [], [], []]
    trace.op_scopes(rec)
    assert [len(op) for op in dev["XLA Ops"]] == [4, 3, 3, 3]


def test_op_scopes_without_hlo_leave_the_ops_alone():
    rec = hand_made()
    assert trace.op_scopes(rec) == hand_made()
    with gzip.open(FIXTURE, "rt") as f:
        fixture = json.load(f)
    for lines in trace.op_scopes(fixture)["devices"].values():
        assert all(len(op) == 3 for op in lines["XLA Ops"])


def test_ops_with_scopes_read_as_without():
    plain = hand_made()
    scoped = hand_made()
    ops = scoped["devices"]["/device:TPU:0"]["XLA Ops"]
    for i, op in enumerate(ops):
        if i % 2 == 0:
            op.append(f"jit(_decode)/while/body/layer{i}")
    lo, hi = trace.host_span(plain, "bench.window")
    assert trace.busy(scoped, lo, hi) == trace.busy(plain, lo, hi)
    assert trace.top_ops(scoped, lo, hi) == trace.top_ops(plain, lo, hi)
    assert trace.idle_by_span(scoped, lo, hi) == \
        trace.idle_by_span(plain, lo, hi)
