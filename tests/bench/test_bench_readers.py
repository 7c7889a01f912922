"""The device readers through a ``run.Ctx`` over the traces recorded on a
TPU v5e (fixtures/), with hand-made step records laid on their
``bench.step`` spans. The values are those the readers gave before the
operation counts moved into bench/arch/, to the last bit."""
import gzip
import json
from pathlib import Path

import pytest

import smoke
from bench import run, trace
from bench.client import StepRec

FIXTURES = Path(__file__).resolve().parent / "fixtures"


def ctx_over(fixture):
    with gzip.open(FIXTURES / fixture, "rt") as f:
        rec = json.load(f)
    conf = json.loads((smoke.ROOT / "bench" / "configs"
                       / "internlm2-1.8b.json").read_text())
    n = max(k for *_, k in trace.step_spans(rec)) + 1
    steps = []
    for k in range(n):
        st = StepRec(idx=k, start=0.0)
        st.decode_positions = [(37 * k + 113 * j) % 2000 for j in range(16)]
        st.prefill_lens = [128 << (k % 4)] if k % 2 == 0 else []
        steps.append(st)
    return run.Ctx(trace=rec, trace_window=trace.host_span(rec,
                                                           "bench.window"),
                   steps=steps, model=conf["model"],
                   arch=run.module("arch", conf["arch"]), peak=smoke.PEAK)


@pytest.mark.parametrize("fixture,metric,want", [
    ("trace_v5e.json.gz", "decode_roofline", 14.749199133227297),
    ("trace_v5e.json.gz", "mfu_pct", 6.616329969613724),
    ("trace_v5e.json.gz", "prefill_ms_per_ktok", None),
    ("trace_v5e.json.gz", "device_idle_pct", 5.752786179883829),
    ("trace_v5e_program.json.gz", "decode_roofline", 13.945001243416808),
    ("trace_v5e_program.json.gz", "mfu_pct", 1.2365510221016265),
    ("trace_v5e_program.json.gz", "prefill_ms_per_ktok", 107.2927421875),
    ("trace_v5e_program.json.gz", "device_idle_pct", 5.763829874901849),
])
def test_device_readers_read_as_before(fixture, metric, want):
    assert run.reader(metric)(ctx_over(fixture)) == want
    assert run.reader(f"{metric}.noisy")(ctx_over(fixture)) == want


@pytest.mark.parametrize("metric,want", [
    ("admit_idle_ms", 0.74323725), ("step_host_idle_ms", 2.1631555)])
def test_program_idle_readers(metric, want):
    """Four steps of internlm2-1.8b.chat with the program's regions; the
    fixture without regions gives nothing to read."""
    ctx = ctx_over("trace_v5e_program.json.gz")
    assert run.reader(metric)(ctx) == pytest.approx(want)
    assert run.reader(f"{metric}.noisy")(ctx) == run.reader(metric)(ctx)
    assert run.reader(metric)(ctx_over("trace_v5e.json.gz")) is None
    ctx.trace = None
    assert run.reader(metric)(ctx) is None
