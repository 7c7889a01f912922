"""Reduction of a profiler trace to device time, idle share and spans.

Stage 1 (``load``) reads the ``.xplane.pb`` that ``jax.profiler`` wrote
and keeps what the metrics read: each TPU plane's "XLA Ops" and
"XLA Modules" lines, the benchmark's own host spans (names starting with
``bench.``) and the program's own regions (``nk.``, which
``repro.obs.tracing.ProfilerTracer`` opens). Stage 2 works on that plain
record, which is also the format of the recorded fixtures the tests use.
All times are nanoseconds on the profiler's clock, which host spans and
device events share.

An op event may carry a fourth element: the name-scope path of its HLO
instruction (the instruction's ``metadata.op_name``, such as
``jit(_decode)/while/body/closed_call/checkpoint/scatter``). On a TPU v5e
(jax 0.9.0) no stat of an op event carries it (they hold only
``device_offset_ps``, ``device_duration_ps`` and ``Time Scale
Multiplier``), and the event's name is the HLO text without metadata. The
profile keeps each program's HLO in its ``/host:metadata`` plane instead
(stat ``Hlo Proto``, one event metadata per program, named as the
"XLA Modules" events are). ``load`` keeps that plane's bytes under
``rec["hlo"]`` and joins nothing; a reader that needs scopes calls
``op_scopes(rec)``, which reads the protos (``plane_scopes``) and joins an
op to its instruction by the program whose execution holds the op and by
the op's name. Readers that need none pay nothing.
"""
from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, Iterator, List, Optional, Tuple

Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


def load(log_dir: str) -> Dict:
    """The newest ``.xplane.pb`` under ``log_dir`` as a plain record:
    ``{"devices": {plane: {line: [[name, start_ns, dur_ns], ...]}},
    "host": [[name, start_ns, dur_ns, {stat: value}], ...],
    "program": [...], "hlo": bytes}``: ``host`` the ``bench.`` spans,
    ``program`` the ``nk.`` regions, in the same form, and ``hlo`` the
    ``/host:metadata`` plane, which ``op_scopes`` reads."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    with open(paths[-1], "rb") as f:
        raw = f.read()
    pd = ProfileData.from_serialized_xspace(raw)
    out: Dict = {"devices": {}, "host": [], "program": [],
                 "hlo": metadata_plane(raw)}
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
                    key = "host" if e.name.startswith("bench.") else \
                        "program" if e.name.startswith("nk.") else None
                    if key:
                        out[key].append([e.name, e.start_ns, e.duration_ns,
                                         {k: v for k, v in e.stats}])
    return out


def op_scopes(rec: Dict) -> Dict:
    """Give each op event of ``rec`` the name-scope path of its HLO
    instruction as a fourth element, where its program has one, and
    return ``rec``. Reads the HLO that ``load`` kept under ``rec["hlo"]``
    and drops it, so a second call, or a record with no HLO (such as the
    recorded fixtures), leaves the ops as they are."""
    scopes = plane_scopes(rec.pop("hlo", b""))
    if scopes:
        for lines in rec["devices"].values():
            add_scopes(lines.get(OPS_LINE, []), lines.get(MODULES_LINE, []),
                       scopes)
    return rec


def add_scopes(ops: List, modules: List,
               scopes: Dict[str, Dict[str, str]]) -> None:
    """Append to each op of ``ops`` the name-scope path of its instruction
    in the program (of ``modules``) whose execution holds the op's start,
    where ``scopes`` (``plane_scopes``) has one."""
    mods = sorted((s, s + d, n) for n, s, d in modules)
    starts = [s for s, _, _ in mods]
    for op in ops:
        k = bisect.bisect_right(starts, op[1]) - 1
        if k < 0 or op[1] > mods[k][1]:
            continue
        path = scopes.get(mods[k][2], {}).get(op_name(op[0]))
        if path:
            op.append(path)


def _fields(b: bytes, lo: int, hi: int) -> Iterator[Tuple[int, object]]:
    """The fields of the protobuf message in b[lo:hi] as (number, value):
    an int, or (start, end) of a length-delimited value."""
    i = lo
    while i < hi:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at {i}")
        yield key >> 3, v


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _text(b: bytes, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def metadata_plane(raw: bytes) -> bytes:
    """The bytes of the ``/host:metadata`` plane of a serialized XSpace
    (XSpace.planes 1, XPlane.name 2); empty where it has none."""
    for f, plane in _fields(raw, 0, len(raw)):
        if f == 1 and next((_text(raw, v) for g, v in _fields(raw, *plane)
                            if g == 2), None) == METADATA_PLANE:
            return raw[plane[0]:plane[1]]
    return b""


def plane_scopes(plane: bytes) -> Dict[str, Dict[str, str]]:
    """``{program: {instruction: op_name}}`` from the HLO protos of a
    serialized ``/host:metadata`` XPlane. Field numbers: XPlane
    event_metadata 4, stat_metadata 5 (map entries: key 1, value 2);
    XEventMetadata.name 2, stats 5; XStat.metadata_id 1, bytes_value 6;
    XStatMetadata.name 2; HloProto.hlo_module 1;
    HloModuleProto.computations 3; HloComputationProto.instructions 2;
    HloInstructionProto.name 1, metadata 7; OpMetadata.op_name 2."""
    out: Dict[str, Dict[str, str]] = {}
    stat_names, metas = {}, []
    for g, v in _fields(plane, 0, len(plane)):
        if g in (4, 5):
            entry = dict(_fields(plane, *v))
            if g == 5 and 2 in entry:
                stat_names[entry.get(1, 0)] = next(
                    (_text(plane, x) for h, x in _fields(plane, *entry[2])
                     if h == 2), "")
            elif g == 4 and 2 in entry:
                metas.append(entry[2])
    for meta in metas:
        program, protos = None, []
        for g, v in _fields(plane, *meta):
            if g == 2:
                program = _text(plane, v)
            elif g == 5:
                stat = dict(_fields(plane, *v))
                if stat_names.get(stat.get(1)) == HLO_PROTO_STAT \
                        and 6 in stat:
                    protos.append(stat[6])
        for proto in protos:
            out.setdefault(program, {}).update(_instruction_scopes(
                plane, proto))
    return out


def _instruction_scopes(raw: bytes, proto) -> Dict[str, str]:
    out = {}
    for f, module in _fields(raw, *proto):
        if f != 1:
            continue
        for g, comp in _fields(raw, *module):
            if g != 3:
                continue
            for h, inst in _fields(raw, *comp):
                if h != 2:
                    continue
                name = path = None
                for k, v in _fields(raw, *inst):
                    if k == 1:
                        name = _text(raw, v)
                    elif k == 7:
                        path = next((_text(raw, x) for j, x in
                                     _fields(raw, *v) if j == 2), None)
                if name and path:
                    out[name] = path
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
        u = union([(s, s + d) for _, s, d, *_ in evs], lo, hi)
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
    for n, s, d, *_ in evs:
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
