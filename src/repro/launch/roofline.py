"""Roofline analysis from compiled artifacts (TPU v5e target, CPU container).

Three terms per (arch x shape x mesh) cell — see DESIGN.md §7:

  compute term    = HLO_FLOPs_per_chip / PEAK_FLOPS
  memory term     = HLO_bytes_per_chip / HBM_BW
  collective term = collective_payload_bytes_per_chip / ICI_BW

FLOPs/bytes come from *layer-differencing probes*: XLA's ``cost_analysis``
counts a ``while`` (scan) body once and reports per-device numbers (verified
empirically), so we lower the same step at two unrolled depths and take the
difference as the exact per-layer cost:  total = fixed + sum_seg count*per.

Collective bytes come from walking the compiled HLO text: computations are
parsed, ``while`` bodies are multiplied by their ``known_trip_count`` (XLA
records it in backend_config), and every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute contributes its payload
bytes. The CoreEngine trace-time ledger cross-checks intent counts.
"""
from __future__ import annotations

import dataclasses
import json
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

# --- hardware constants (TPU v5e, per chip) ---
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link
HBM_BYTES = 16 * 1024 ** 3   # 16 GiB

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for tok in dims.split(","):
        tok = tok.strip()
        if tok:
            n *= int(tok)
    return n * _DTYPE_BYTES.get(dtype, 4)


@dataclass
class CollectiveOp:
    kind: str
    dtype: str
    bytes: int
    computation: str
    multiplier: int = 1


# NOTE: computation params may be tuple-typed (nested parens) — match
# greedily up to the last ') ->' on the header line.
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->", re.M)
_OP_SHAPE_RE = re.compile(
    r"=\s*(?:\()?([a-z0-9]+)\[([\d,\s]*)\][^\n]*?\b(" + "|".join(COLLECTIVES) + r")\(")
_CALLED_ONE_RE = re.compile(
    r"(?:to_apply|body|condition|calls|true_computation|"
    r"false_computation)=%?([\w\.\-]+)")
_CALLED_LIST_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_TRIP_RE = re.compile(r'known_trip_count[^\d]*(\d+)')


def _called_names(line: str):
    names = _CALLED_ONE_RE.findall(line)
    for group in _CALLED_LIST_RE.findall(line):
        names.extend(n.strip().lstrip("%") for n in group.split(","))
    return [n for n in names if n]


def parse_hlo_collectives(hlo_text: str) -> List[CollectiveOp]:
    """Collectives with trip-count multipliers from compiled HLO text."""
    # split into computations
    comps: Dict[str, List[str]] = {}
    entry = None
    cur = None
    for line in hlo_text.splitlines():
        m = _COMP_RE.match(line.strip())
        if m and line.rstrip().endswith("{"):
            cur = m.group(1)
            comps[cur] = []
            if line.strip().startswith("ENTRY"):
                entry = cur
            continue
        if cur is not None:
            if line.strip() == "}":
                cur = None
            else:
                comps[cur].append(line)

    # call graph with per-edge multiplier (while bodies x trip_count)
    edges: Dict[str, List[Tuple[str, int]]] = defaultdict(list)
    for name, lines in comps.items():
        for line in lines:
            called = _called_names(line)
            if not called:
                continue
            mult = 1
            if re.search(r"\bwhile\(", line):
                t = _TRIP_RE.search(line)
                mult = int(t.group(1)) if t else 1
            for c in called:
                if c in comps:
                    edges[name].append((c, mult))

    # multiplier per computation (DFS from entry; DAG-ish, cycles guarded)
    mults: Dict[str, int] = defaultdict(int)

    def walk(name: str, m: int, depth=0):
        if depth > 50:
            return
        mults[name] += m
        for child, em in edges.get(name, []):
            walk(child, m * em, depth + 1)

    if entry:
        walk(entry, 1)
    else:  # fallback: everything counted once
        for name in comps:
            mults[name] = 1

    out: List[CollectiveOp] = []
    for name, lines in comps.items():
        mult = mults.get(name, 0)
        if mult == 0:
            continue
        for line in lines:
            m = _OP_SHAPE_RE.search(line)
            if m:
                dt, dims, kind = m.groups()
                out.append(CollectiveOp(kind=kind, dtype=dt,
                                        bytes=_shape_bytes(dt, dims),
                                        computation=name, multiplier=mult))
    return out


def collective_bytes(hlo_text: str) -> Tuple[int, Dict[str, int]]:
    ops = parse_hlo_collectives(hlo_text)
    per_kind: Dict[str, int] = defaultdict(int)
    for op in ops:
        per_kind[op.kind] += op.bytes * op.multiplier
    return sum(per_kind.values()), dict(per_kind)


# ---------------------------------------------------------------------------
# Post-fusion HBM traffic from HLO text.
#
# XLA's CPU HloCostAnalysis reports pre-fusion "bytes accessed" (~10x real
# traffic — measured), so we account bytes ourselves on the *optimized*
# module: every op in a non-fused computation contributes its output bytes
# plus its operands' bytes (shapes resolved through a def-map); computations
# reachable only through ``fusion(...)`` calls are interior (free); while
# bodies are multiplied by their known trip count. This mirrors what
# HloCostAnalysis does on TPU, where fusions hide interior traffic.
# ---------------------------------------------------------------------------

_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(\(?[^=]*?)\s([a-z][a-z0-9\-]*)\(")
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([\d,\s]*)\]")
_OPERAND_RE = re.compile(r"%([\w\.\-]+)")
_SKIP_OPS = {"parameter", "get-tuple-element", "tuple", "bitcast", "constant",
             "after-all", "partition-id", "replica-id", "iota"}


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt in _DTYPE_BYTES:
            n = 1
            for tok in dims.split(","):
                tok = tok.strip()
                if tok:
                    n *= int(tok)
            total += n * _DTYPE_BYTES[dt]
    return total


def hlo_traffic_bytes(hlo_text: str) -> int:
    """Estimated per-chip HBM traffic (bytes/step) from compiled HLO."""
    comps: Dict[str, List[str]] = {}
    entry = None
    cur = None
    for line in hlo_text.splitlines():
        m = _COMP_RE.match(line.strip())
        if m and line.rstrip().endswith("{"):
            cur = m.group(1)
            comps[cur] = []
            if line.strip().startswith("ENTRY"):
                entry = cur
            continue
        if cur is not None:
            if line.strip() == "}":
                cur = None
            else:
                comps[cur].append(line)

    # def-map: value name -> bytes; call edges; fusion-interior set
    sizes: Dict[str, int] = {}
    interior: set = set()
    edges: Dict[str, List[Tuple[str, int]]] = defaultdict(list)
    for name, lines in comps.items():
        for line in lines:
            d = _DEF_RE.match(line)
            if d:
                vname, vtype, op = d.groups()
                sizes[vname] = _type_bytes(vtype)
            called = _called_names(line)
            if called:
                mult = 1
                is_fusion = bool(re.search(r"\bfusion\(", line))
                if re.search(r"\bwhile\(", line):
                    t = _TRIP_RE.search(line)
                    mult = int(t.group(1)) if t else 1
                for c in called:
                    if c in comps:
                        if is_fusion:
                            interior.add(c)
                        else:
                            edges[name].append((c, mult))

    mults: Dict[str, int] = defaultdict(int)

    def walk(nm, m, depth=0):
        if depth > 50:
            return
        mults[nm] += m
        for child, em in edges.get(nm, []):
            walk(child, m * em, depth + 1)

    if entry:
        walk(entry, 1)
    else:
        for nm in comps:
            mults[nm] = 1

    total = 0
    for name, lines in comps.items():
        if name in interior:
            continue
        mult = mults.get(name, 0)
        if mult == 0:
            continue
        for line in lines:
            d = _DEF_RE.match(line)
            if not d:
                continue
            vname, vtype, op = d.groups()
            if op in _SKIP_OPS or op in ("while", "conditional", "call"):
                continue  # control ops: bodies accounted via multipliers
            out_b = sizes.get(vname, 0)
            # operands: names after the op's open paren
            tail = line.split(op + "(", 1)[1] if op + "(" in line else ""
            tail = tail.split("),", 1)[0]
            in_b = sum(sizes.get(o, 0) for o in _OPERAND_RE.findall(tail))
            total += (out_b + in_b) * mult
    return total


# ---------------------------------------------------------------------------
# Roofline assembly
# ---------------------------------------------------------------------------


@dataclass
class RooflineCell:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_chip: float
    hbm_bytes_per_chip: float
    coll_bytes_per_chip: float
    coll_by_kind: Dict[str, int]
    model_flops_global: float
    memory_per_chip_gb: float
    compile_seconds: float
    ideal_bytes_global: float = 0.0
    skipped: bool = False
    skip_reason: str = ""
    notes: str = ""

    @property
    def t_compute(self) -> float:
        return self.flops_per_chip / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes_per_chip / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes_per_chip / ICI_BW

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        hlo_global = self.flops_per_chip * self.chips
        return self.model_flops_global / hlo_global if hlo_global else 0.0

    @property
    def t_ideal(self) -> float:
        """Roofline floor: the better of the compute and memory walls for
        the *useful* work (model FLOPs / minimal bytes)."""
        return max(self.model_flops_global / (self.chips * PEAK_FLOPS),
                   self.ideal_bytes_global / (self.chips * HBM_BW))

    @property
    def roofline_fraction(self) -> float:
        """t_ideal / modeled step time (max of the three terms, perfect
        overlap assumed) — the score we hillclimb, meaningful for both
        compute-bound (train) and memory-bound (decode) cells."""
        t = max(self.t_compute, self.t_memory, self.t_collective)
        if t <= 0:
            return 0.0
        return min(self.t_ideal / t, 1.0)

    def to_json(self) -> Dict:
        d = dataclasses.asdict(self)
        d.update(t_compute=self.t_compute, t_memory=self.t_memory,
                 t_collective=self.t_collective, dominant=self.dominant,
                 useful_ratio=self.useful_ratio,
                 roofline_fraction=self.roofline_fraction)
        return d


def model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6*N_active*D (train), 2*N_active*D (prefill),
    2*N_active*B (decode, per step)."""
    n = cfg.num_active_params()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch


def cache_bytes_global(cfg, shape, dtype_bytes: int = 2) -> float:
    """Decode-cell KV/state cache size (the floor of decode HBM traffic)."""
    b, s = shape.global_batch, shape.seq_len
    total = 0.0
    mla = cfg.mla
    for i in range(cfg.num_layers):
        window = 0
        if cfg.attn_window and i not in cfg.global_attn_layers:
            window = cfg.attn_window
        n_slots = min(s, window) if window else s
        if cfg.family == "ssm":
            pass
        elif mla is not None:
            total += b * n_slots * (mla.kv_lora_rank + mla.qk_rope_head_dim) \
                * dtype_bytes
        elif cfg.family in ("dense", "moe", "vlm", "encdec", "hybrid"):
            total += 2 * b * n_slots * cfg.num_kv_heads * cfg.head_dim \
                * dtype_bytes
        if cfg.ssm is not None:
            ss = cfg.ssm
            total += b * ss.heads(cfg.d_model) * ss.head_dim \
                * ss.state_dim * 4
    return total


def ideal_bytes(cfg, shape) -> float:
    """Global minimal HBM traffic per step (documented floor, not a bound
    proof): weights read fwd(+remat+bwd for train), optimizer state r/w,
    a small per-layer activation budget, plus the full cache for decode."""
    n = cfg.num_active_params()
    n_tot = cfg.num_params()
    b, s = shape.global_batch, shape.seq_len
    act = 6.0 * b * s * cfg.d_model * 2 * cfg.num_layers
    if shape.kind == "train":
        return 3 * 2 * n + 10 * n_tot + act     # weights x3, opt state r/w
    if shape.kind == "prefill":
        return 2 * n + act + cache_bytes_global(cfg, shape)
    act = 6.0 * b * 1 * cfg.d_model * 2 * cfg.num_layers
    return 2 * n + act + cache_bytes_global(cfg, shape)


def fmt_seconds(s: float) -> str:
    if s >= 1:
        return f"{s:.2f}s"
    if s >= 1e-3:
        return f"{s * 1e3:.2f}ms"
    return f"{s * 1e6:.1f}us"


def markdown_table(cells: List[RooflineCell]) -> str:
    hdr = ("| arch | shape | mesh | dominant | t_compute | t_memory | "
           "t_collective | useful | roofline | mem/chip |\n"
           "|---|---|---|---|---|---|---|---|---|---|\n")
    rows = []
    for c in cells:
        if c.skipped:
            rows.append(f"| {c.arch} | {c.shape} | {c.mesh} | SKIP | - | - | "
                        f"- | - | - | - |")
            continue
        rows.append(
            f"| {c.arch} | {c.shape} | {c.mesh} | **{c.dominant}** | "
            f"{fmt_seconds(c.t_compute)} | {fmt_seconds(c.t_memory)} | "
            f"{fmt_seconds(c.t_collective)} | {c.useful_ratio:.2f} | "
            f"{c.roofline_fraction:.2%} | {c.memory_per_chip_gb:.2f} GB |")
    return hdr + "\n".join(rows)
