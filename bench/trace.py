"""Reduction of a profiler trace to device time, idle share and spans.

Stage 1 (``load``) reads the ``.xplane.pb`` that ``jax.profiler`` wrote
and keeps what the metrics read: each TPU plane's "XLA Ops" and
"XLA Modules" lines, and the benchmark's own host spans (names starting
with ``bench.``). Stage 2 works on that plain record, which is also the
format of the recorded fixture the tests use. All times are nanoseconds
on the profiler's clock, which host spans and device events share.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def load(log_dir: str) -> Dict:
    """The newest ``.xplane.pb`` under ``log_dir`` as a plain record:
    ``{"devices": {plane: {line: [[name, start_ns, dur_ns], ...]}},
    "host": [[name, start_ns, dur_ns, {stat: value}], ...]}``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    out: Dict = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [[e.name, e.start_ns, e.duration_ns]
                                        for e in line.events]
            out["devices"][plane.name] = lines
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        out["host"].append([e.name, e.start_ns,
                                            e.duration_ns,
                                            {k: v for k, v in e.stats}])
    return out


def union(intervals: List[Interval], lo: float, hi: float
          ) -> List[Interval]:
    """Merged intervals, clipped to [lo, hi]."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def host_span(rec: Dict, name: str) -> Optional[Interval]:
    """The first host span called ``name``, as (start, end)."""
    for n, s, d, _ in rec["host"]:
        if n == name:
            return (s, s + d)
    return None


def _device_lines(rec: Dict, line: str) -> Dict[str, List]:
    return {p: lines.get(line, []) for p, lines in rec["devices"].items()}


def busy(rec: Dict, lo: float, hi: float) -> Dict:
    """Busy and idle intervals of each device inside [lo, hi]: the union of
    the intervals in which an operation ran, from the ops line (or the
    modules line where a plane has no ops)."""
    out = {}
    for plane, lines in rec["devices"].items():
        evs = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        u = union([(s, s + d) for _, s, d in evs], lo, hi)
        out[plane] = u
    return out


def busy_seconds(rec: Dict, lo: float, hi: float) -> float:
    """Device-busy seconds inside [lo, hi], averaged over the chips."""
    per = busy(rec, lo, hi)
    if not per:
        return 0.0
    return sum(sum(e - s for s, e in u) for u in per.values()) \
        / len(per) / 1e9


def gaps(u: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in u:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def host_timeline(host: List) -> List[Tuple[float, float, str]]:
    """The host's time cut into pieces, each labeled with the innermost
    benchmark span open over it (the spans nest: one thread)."""
    spans = sorted(((s, s + d, n) for n, s, d, _ in host),
                   key=lambda x: (x[0], -x[1]))
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, float, str]] = []
    t = spans[0][0] if spans else 0.0

    def close_until(limit):
        nonlocal t
        while stack and stack[-1][1] <= limit:
            s, e, n = stack.pop()
            if e > t:
                out.append((t, e, n))
                t = e

    for s, e, n in spans:
        close_until(s)
        if stack and s > t:
            out.append((t, s, stack[-1][2]))
        t = max(t, s)
        stack.append((s, e, n))
    close_until(float("inf"))
    return out


def idle_by_span(rec: Dict, lo: float, hi: float, top: int = 10) -> List:
    """Device-idle seconds inside [lo, hi], summed by the innermost host
    span open over each idle piece: what the host was doing while the chip
    idled. The first chip's, largest first."""
    per = busy(rec, lo, hi)
    if not per:
        return []
    u = per[sorted(per)[0]]
    host = [h for h in rec["host"] if h[0] != "bench.window"]
    pieces = host_timeline(host)
    tot: Dict[str, float] = {}
    j = 0
    for s, e in gaps(u, lo, hi):
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            a, b = max(s, pieces[k][0]), min(e, pieces[k][1])
            if b > a:
                tot[pieces[k][2]] = tot.get(pieces[k][2], 0.0) \
                    + (b - a) / 1e9
                covered += b - a
            k += 1
        if e - s > covered:
            tot["host:outside-spans"] = tot.get("host:outside-spans", 0.0) \
                + (e - s - covered) / 1e9
    return sorted(([k, v] for k, v in tot.items()),
                  key=lambda kv: -kv[1])[:top]


CONTAINERS = ("while", "conditional", "call")


def op_name(hlo: str) -> str:
    """An op's name from its event name, which is the HLO instruction's
    text: ``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def top_ops(rec: Dict, lo: float, hi: float, top: int = 10) -> List:
    """Device seconds by operation name inside [lo, hi], first chip,
    largest first. Loops and calls are left out: the ops they run are on
    the same line, inside them."""
    lines = _device_lines(rec, OPS_LINE)
    if not lines:
        return []
    evs = lines[sorted(lines)[0]]
    tot: Dict[str, float] = {}
    for n, s, d in evs:
        name = op_name(n)
        if s >= lo and s + d <= hi and not name.startswith(CONTAINERS):
            tot[name] = tot.get(name, 0.0) + d / 1e9
    return sorted(([k, v] for k, v in tot.items()),
                  key=lambda kv: -kv[1])[:top]


def module_events(rec: Dict, prefix: str, lo: float, hi: float) -> List:
    """Module (program) executions whose name starts with ``prefix``
    inside [lo, hi], first chip: [[name, start_ns, dur_ns], ...]."""
    lines = _device_lines(rec, MODULES_LINE)
    if not lines:
        return []
    evs = lines[sorted(lines)[0]]
    return [e for e in evs if e[0].startswith(prefix)
            and e[1] >= lo and e[1] + e[2] <= hi]


def step_spans(rec: Dict) -> List[Tuple[float, float, int]]:
    """The ``bench.step`` host spans as sorted (start, end, step index)."""
    return sorted((s, s + d, int(st.get("step", -1)))
                  for n, s, d, st in rec["host"] if n == "bench.step")


def step_at(spans: List[Tuple[float, float, int]], t: float
            ) -> Optional[int]:
    """The index of the step whose span (from ``step_spans``) holds t."""
    i = bisect.bisect_right(spans, (t, float("inf"), 0)) - 1
    if i >= 0 and spans[i][0] <= t <= spans[i][1]:
        return spans[i][2]
    return None
