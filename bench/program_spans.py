"""The program's own regions in a profile, and the device's idle time put
down to them.

``repro.obs.tracing.ProfilerTracer`` turns the served path's regions into
profiler annotations named ``nk.<track>.<name>``, on the clock the
device's events share. ``bench/run.py`` keeps the ``NullTracer`` in its
traced runs and ``bench/trace.py`` keeps only the benchmark's ``bench.*``
host spans, so no metric of ``BENCHMARK.json`` reads the regions yet
(PERF.md, Open questions). This module reads them, and its command runs
one cell as ``bench/run.py --trace 1`` does with the regions on:

  python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s>

It prints ``bench/run.py``'s output, whose result line reads as a traced
run's, then one JSON line: the device-idle milliseconds per
``nk.engine.step`` under each group of ``GROUPS``, and the idle seconds
under the control tick, under no region, and in all.
"""
from __future__ import annotations

import contextlib
import glob
import json
import os
import sys
from pathlib import Path
from typing import Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import trace  # noqa: E402

STEP = "nk.engine.step"
TICK = "nk.control.tick"
OUTSIDE = "host:outside-spans"
# the innermost regions each share of a step's device idle is put down to
GROUPS = {
    "readback_idle_ms": ("nk.engine.readback",),
    "admit_idle_ms": ("nk.engine.admit", "nk.scheduler.pick",
                      "nk.engine.prefill", "nk.engine.install",
                      "nk.engine.first_token"),
    "step_host_idle_ms": (STEP, "nk.engine.prepare", "nk.engine.decode",
                          "nk.engine.commit"),
}


def load(log_dir: str) -> List:
    """The ``nk.`` host events of the newest ``.xplane.pb`` under
    ``log_dir``, as ``[[name, start_ns, dur_ns, {stat: value}], ...]``:
    the form of ``bench/trace.py``'s ``rec["host"]``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                out.extend([e.name, e.start_ns, e.duration_ns,
                            {k: v for k, v in e.stats}]
                           for e in line.events if e.name.startswith("nk."))
    return out


def idle_by_program_span(rec: Dict, lo: float, hi: float
                         ) -> Dict[str, float]:
    """Device-idle seconds inside [lo, hi], first chip, by the innermost
    region of ``rec["program"]`` open over each idle piece; idle time under
    no region is ``host:outside-spans``. Empty where the record has no
    device or no regions."""
    program = rec.get("program") or []
    if not program:
        return {}
    return dict(trace.idle_by_span(dict(rec, host=program), lo, hi,
                                   top=None))


def program_steps(rec: Dict, lo: float, hi: float) -> int:
    """The number of ``nk.engine.step`` regions inside [lo, hi]."""
    return sum(1 for n, s, d, _ in rec.get("program") or []
               if n == STEP and s >= lo and s + d <= hi)


def idle_ms_per_step(rec: Dict, lo: float, hi: float,
                     names) -> Optional[float]:
    """Device-idle milliseconds under the regions ``names`` (innermost)
    inside [lo, hi], per ``nk.engine.step`` there; None where the record
    has no steps, no regions or no device."""
    steps = program_steps(rec, lo, hi)
    idle = idle_by_program_span(rec, lo, hi)
    if not steps or not idle:
        return None
    return sum(idle.get(n, 0.0) for n in names) / steps * 1e3


def split(rec: Dict, lo: float, hi: float) -> Optional[Dict]:
    """The window's device idle put down to the program's regions: each
    group's milliseconds per step, the steps, and the idle seconds under
    the tick, under no region, and in all; None where there is nothing to
    read."""
    idle = idle_by_program_span(rec, lo, hi)
    steps = program_steps(rec, lo, hi)
    if not idle or not steps:
        return None
    out = {g: sum(idle.get(n, 0.0) for n in names) / steps * 1e3
           for g, names in GROUPS.items()}
    out.update(steps=steps, tick_idle_s=idle.get(TICK, 0.0),
               outside_idle_s=idle.get(OUTSIDE, 0.0),
               idle_s=sum(idle.values()))
    return out


@contextlib.contextmanager
def regions_on():
    """The program's ``ProfilerTracer`` installed, and ``bench/trace.py``'s
    loader made to keep the regions under ``rec["program"]`` beside what
    it keeps; both put back on exit. Yields a dict that holds the last
    record loaded under ``"rec"``."""
    from repro.obs import tracing
    kept: Dict = {}
    bench_load = trace.load

    def load_with_regions(log_dir):
        rec = bench_load(log_dir)
        rec["program"] = load(log_dir)
        kept["rec"] = rec
        return rec

    prev = tracing.set_tracer(tracing.ProfilerTracer())
    trace.load = load_with_regions
    try:
        yield kept
    finally:
        trace.load = bench_load
        tracing.set_tracer(prev)


def main(argv=None) -> int:
    from bench import run
    argv = list(sys.argv[1:] if argv is None else argv)
    with regions_on() as kept:
        rc = run.main(argv + ["--trace", "1"])
    rec = kept.get("rec")
    if rc == 0 and rec is not None:
        lo, hi = trace.host_span(rec, "bench.window")
        print(json.dumps({"program_idle": split(rec, lo, hi)}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
