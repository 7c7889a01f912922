"""The device's idle time put down to the program's own regions.

``repro.obs.tracing.ProfilerTracer`` turns the served path's regions into
profiler annotations named ``nk.<track>.<name>``, on the clock the
device's events share; a traced run of ``bench/run.py`` installs it, logs
the whole ``split``, and ``bench/trace.py`` keeps the regions under
``rec["program"]``. The readers ``admit_idle_ms`` and
``step_host_idle_ms`` (and their ``.noisy`` twins) read ``GROUPS`` with
``idle_ms_per_step``.

The step's host work is one group, the read-back with it: the profiler
puts the device's events up to about a millisecond off the host's
regions, by an amount that changes from capture to capture, and that
moves idle between the read-back and the next step's dispatch while
their sum holds. A finer split waits for the two clocks to be aligned.
"""
from __future__ import annotations

from typing import Dict, Optional

from bench import trace

STEP = "nk.engine.step"
TICK = "nk.control.tick"
OUTSIDE = "host:outside-spans"
# the innermost regions each share of a step's device idle is put down to
GROUPS = {
    "admit_idle_ms": ("nk.engine.admit", "nk.scheduler.pick",
                      "nk.engine.prefill", "nk.engine.install",
                      "nk.engine.first_token"),
    "step_host_idle_ms": (STEP, "nk.engine.prepare", "nk.engine.decode",
                          "nk.engine.readback", "nk.engine.commit"),
}


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

