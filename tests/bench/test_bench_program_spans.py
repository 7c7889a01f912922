"""The program's own regions (``nk.*``) on the profiler's clock, as
bench/trace.py keeps them, and the device idle put down to them
(bench/program_spans.py): a smoke serving path stepped under
``jax.profiler`` on the CPU, with the benchmark's instance wrappers in
place as a traced run has them; hand-made records; and a small trace
recorded on a TPU v5e (fixtures/trace_v5e_program.json.gz)."""
import gzip
import json
from pathlib import Path

import jax
import numpy as np
import pytest

import smoke
from bench import program_spans, run, trace, traffic, weights
from bench.client import Serving, clock
from repro.obs import tracing
from test_bench_trace import hand_made

STEP_PARTS = ["nk.engine.admit", "nk.engine.prepare", "nk.engine.decode",
              "nk.engine.readback", "nk.engine.commit"]
ADMIT_PARTS = ["nk.scheduler.pick", "nk.engine.prefill",
               "nk.engine.install", "nk.engine.first_token"]
FIXTURE = Path(__file__).resolve().parent / "fixtures" \
    / "trace_v5e_program.json.gz"


@pytest.fixture(scope="module")
def served():
    conf, mix = smoke.smoke_conf(), smoke.smoke_mix()
    m = conf["model"]
    arch = run.module("arch", conf["arch"])
    w = weights.make_weights(arch.layout(m), m["param_dtype"], 7)
    return Serving(conf, traffic.weights(mix), arch.program_params(w, m))


def _submit(srv, n, rid0):
    rng = np.random.default_rng(rid0)
    for k in range(n):
        srv.submit(traffic.Req(rid=rid0 + k, tenant=k % 2, due=clock(),
                               prompt_len=8, out_len=3,
                               prompt=rng.integers(0, 256, 8,
                                                   dtype=np.int32)),
                   clock())


def _traced_steps(srv, tracer, tmp_path, n_steps=4):
    """Step the engine ``n_steps`` times under the profiler with
    ``tracer`` installed; the plain record of that trace (the program's
    regions under ``"program"``)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    prev = tracing.set_tracer(tracer)
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(n_steps):
            srv.step()
    finally:
        jax.profiler.stop_trace()
        tracing.set_tracer(prev)
    return trace.load(str(tmp_path))


def _parent(ev, evs):
    """The innermost other region that encloses ``ev``."""
    n, s, d, _ = ev
    outer = [o for o in evs if o is not ev and o[1] <= s
             and s + d <= o[1] + o[2] and o[2] > d]
    return min(outer, key=lambda o: o[2]) if outer else None


def test_program_regions_nest_as_the_served_path(served, tmp_path):
    srv = served
    srv.eng.steps = 3                      # the next step ticks (every 4th)
    _submit(srv, 2, 1000)
    rec = _traced_steps(srv, tracing.ProfilerTracer(), tmp_path)
    prog = rec["program"]
    names = {e[0] for e in prog}
    assert {"nk.engine.step", "nk.control.tick"} | set(STEP_PARTS) \
        | set(ADMIT_PARTS) <= names
    # the benchmark's own spans are kept apart, as before
    assert all(e[0].startswith("bench.") for e in rec["host"])
    assert {"bench.step", "bench.admit", "bench.readback"} <= \
        {e[0] for e in rec["host"]}
    for ev in prog:
        p = _parent(ev, prog)
        if ev[0] in ADMIT_PARTS:
            assert p[0] == "nk.engine.admit", ev
        elif ev[0] in STEP_PARTS or ev[0] == "nk.control.tick":
            assert p[0] == "nk.engine.step", ev
        else:
            assert ev[0] == "nk.engine.step" and p is None, ev
    steps = sorted(e for e in prog if e[0] == "nk.engine.step")
    assert len(steps) == 4
    first = [e[0] for e in sorted(prog, key=lambda e: e[1])
             if _parent(e, prog) is steps[0]]
    # the first step ticks, admits both requests, then decodes
    assert first == ["nk.control.tick"] + STEP_PARTS
    admits = [e for e in prog if e[0] in ADMIT_PARTS
              and steps[0][1] <= e[1] <= steps[0][1] + steps[0][2]]
    assert [e[0] for e in sorted(admits, key=lambda e: e[1])] == \
        ADMIT_PARTS * 2 + ["nk.scheduler.pick"]
    # the wall-clock path fills the scheduler's wait histogram
    assert sum(srv.sched.admit_wait_hist.get(t).total for t in (0, 1)) >= 2


def test_no_regions_under_the_null_tracer(served, tmp_path):
    srv = served
    _submit(srv, 1, 2000)
    rec = _traced_steps(srv, tracing.NullTracer(), tmp_path, n_steps=2)
    assert rec["program"] == []
    assert any(e[0] == "bench.step" for e in rec["host"])


def hand_made_program():
    """hand_made() with the program's regions: idle [6, 10) and [12, 15)
    fall under readback [6, 8), no region [8, 9), pick [9, 9.5), admit
    [9.5, 10), tick [12, 13), step [13, 14), commit [14, 14.5) and step
    [14.5, 15)."""
    ms = 1_000_000
    rec = hand_made()
    rec["program"] = [
        ["nk.engine.step", 0, 8 * ms, {}],
        ["nk.engine.decode", 1 * ms, 1 * ms, {}],
        ["nk.engine.readback", 5 * ms, 3 * ms, {}],
        ["nk.engine.step", 9 * ms, 11 * ms, {}],
        ["nk.engine.admit", 9 * ms, 2 * ms, {}],
        ["nk.scheduler.pick", 9 * ms, ms // 2, {}],
        ["nk.control.tick", 12 * ms, 1 * ms, {}],
        ["nk.engine.commit", 14 * ms, ms // 2, {}]]
    return rec


def test_idle_by_program_span():
    rec = hand_made_program()
    lo, hi = trace.host_span(rec, "bench.window")
    assert program_spans.idle_by_program_span(rec, lo, hi) == \
        pytest.approx({
            "nk.engine.readback": 0.002, "host:outside-spans": 0.001,
            "nk.scheduler.pick": 0.0005, "nk.engine.admit": 0.0005,
            "nk.control.tick": 0.001, "nk.engine.step": 0.0015,
            "nk.engine.commit": 0.0005})
    assert program_spans.program_steps(rec, lo, hi) == 2
    assert program_spans.program_steps(rec, lo, 19e6) == 1
    # the benchmark's own breakdown is untouched by the program's regions
    assert trace.idle_by_span(rec, lo, hi) == trace.idle_by_span(
        hand_made(), lo, hi)


@pytest.mark.parametrize("group,want", [("admit_idle_ms", 0.5),
                                        ("step_host_idle_ms", 2.0)])
def test_program_idle_per_step_on_hand_made(group, want):
    rec = hand_made_program()
    lo, hi = trace.host_span(rec, "bench.window")
    assert program_spans.idle_ms_per_step(
        rec, lo, hi, program_spans.GROUPS[group]) == pytest.approx(want)
    assert program_spans.split(rec, lo, hi)[group] == pytest.approx(want)


def test_program_idle_without_regions_or_device():
    bare = hand_made()                     # no "program" key at all
    lo, hi = trace.host_span(bare, "bench.window")
    assert program_spans.idle_by_program_span(bare, lo, hi) == {}
    assert program_spans.program_steps(bare, lo, hi) == 0
    assert program_spans.split(bare, lo, hi) is None
    no_dev = dict(hand_made_program(), devices={})
    assert program_spans.split(no_dev, lo, hi) is None
    for names in program_spans.GROUPS.values():
        assert program_spans.idle_ms_per_step(bare, lo, hi, names) is None
        assert program_spans.idle_ms_per_step(no_dev, lo, hi, names) is None


def test_recorded_v5e_program_regions():
    """Four steps of internlm2-1.8b.chat, the third admitting a request,
    recorded on one TPU v5e with the program's regions."""
    with gzip.open(FIXTURE, "rt") as f:
        rec = json.load(f)
    lo, hi = trace.host_span(rec, "bench.window")
    idle_s = (hi - lo) / 1e9 - trace.busy_seconds(rec, lo, hi)
    got = program_spans.split(rec, lo, hi)
    assert got["steps"] == 4
    assert {g: got[g] for g in program_spans.GROUPS} == pytest.approx({
        "admit_idle_ms": 0.74323725, "step_host_idle_ms": 2.1631555})
    assert got["idle_s"] == pytest.approx(idle_s, rel=1e-9)
    # the groups, the tick and the idle under no region make up
    # every idle second of the window
    assert sum(got[g] for g in program_spans.GROUPS) * 4 / 1e3 \
        + got["tick_idle_s"] + got["outside_idle_s"] == \
        pytest.approx(idle_s, rel=1e-9)
    # the benchmark's own breakdown reads the bench.* spans alone
    assert all(n.startswith(("bench.", "host:"))
               for n, _ in trace.idle_by_span(rec, lo, hi))
    assert sum(v for _, v in trace.idle_by_span(rec, lo, hi)) == \
        pytest.approx(idle_s, rel=1e-9)


def test_traced_run_with_regions_reads_as_without(monkeypatch):
    """A traced smoke run installs the program's tracer for the window
    only: it reports the metrics a traced run reports, keeps the regions
    under ``program`` beside the bench.* spans under ``host``, reads the
    engine's counters at both edges of the window, and puts the tracer
    back."""
    _, c, conf, _, e2e, per = run.load_cell("internlm2-1.8b.noisy-neighbour")
    tracers = []
    orig_start = jax.profiler.start_trace

    def start_trace(*a, **k):
        tracers.append(tracing.TRACER)
        return orig_start(*a, **k)
    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    ctxs = smoke.record_ctx(monkeypatch)
    out = run.run_cell(smoke.args(trace=1), c, smoke.smoke_conf(),
                       smoke.smoke_mix("noisy-neighbour"), e2e, per,
                       smoke.PEAK, smoke.DEVICE, lambda m: None)
    assert out["correct"], out["checks"]
    assert [type(t) for t in tracers] == [tracing.ProfilerTracer]
    assert type(tracing.TRACER) is tracing.NullTracer
    # no device plane on the CPU: the device and region readers are silent
    assert set(out["metrics"]) == {
        "queue_wait_p95_s.light", "ttft_p95_s.light",
        "control_tick_ms.noisy", "step_ms.noisy"}
    assert out["breakdown"]["idle_gaps"] == []
    rec = ctxs[0].trace
    assert any(e[0] == "nk.engine.step" for e in rec["program"])
    assert all(e[0].startswith("nk.") for e in rec["program"])
    assert all(e[0].startswith("bench.") for e in rec["host"])
    assert program_spans.split(rec, *ctxs[0].trace_window) is None
    assert ctxs[0].counters_at_open == ctxs[0].counters_at_close == {
        "nk_decode_cache_inplace_segments": 1.0}
