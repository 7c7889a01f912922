"""The one traffic generator: an open-loop schedule from a mix's data file.

A mix (``bench/traffic/<name>.json``) lists tenants with their weights,
the tenants whose latency is judged, a warm-up span, and streams. A stream
sends requests from its tenants at a rate, with prompt and output lengths
drawn from the distributions it names.

Every seed gets the same amount of work. For each span (warm-up, window) a
stream sends N = round(rate x span) requests at the times of a Poisson
process given N arrivals in the span: N instants drawn uniformly from the
seed, in order, so bursts and lulls form as they do in a Poisson process.
Their lengths are the N quantiles of the length distributions, and their
tenants follow the popularity in exact proportion; the seed puts lengths
and tenants in an order of its own, and draws the prompt tokens, uniform
over the vocabulary. So every seed offers the same requests and tokens,
at other times and in another order.

A stream's rate is ``{"rps": x}`` or ``{"knee_share": f}``: f times the
configuration's measured knee (``knee_rps`` in its file), so one mix
serves several configurations at the same relative load.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass, field
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np


@dataclass
class Req:
    """One request on the schedule, and what the client saw of it."""
    rid: int
    tenant: int
    due: float                  # seconds after the schedule's start
    prompt_len: int
    out_len: int
    prompt: Optional[np.ndarray] = None
    picked: Optional[float] = None       # scheduler picked it (client clock)
    token_times: List[float] = field(default_factory=list)
    served: object = None       # the engine's Request, once submitted


def load_mix(path) -> Dict:
    return json.loads(Path(path).read_text())


def stream_rate(stream: Dict, knee_rps: Optional[float]) -> float:
    rate = stream["rate"]
    if "rps" in rate:
        return float(rate["rps"])
    if knee_rps is None:
        raise ValueError("the mix's rate is a share of the knee, and the "
                         "configuration states no knee_rps")
    return float(rate["knee_share"]) * float(knee_rps)


def _largest_remainder(n: int, probs) -> List[int]:
    p = np.asarray(probs, float)
    p = p / p.sum()
    raw = p * n
    counts = np.floor(raw).astype(int)
    short = n - int(counts.sum())
    order = np.argsort(-(raw - counts), kind="stable")
    counts[order[:short]] += 1
    return counts.tolist()


def length_quantiles(spec: Dict, n: int) -> List[int]:
    """n lengths in exact proportion to the distribution ``spec``:
    ``{"values": [...], "probs": [...]}`` or ``{"lognormal_median": m,
    "lognormal_sigma": s, "min": lo, "max": hi}``."""
    if "values" in spec:
        counts = _largest_remainder(n, spec["probs"])
        return [int(v) for v, c in zip(spec["values"], counts)
                for _ in range(c)]
    nd = NormalDist()
    med, sig = float(spec["lognormal_median"]), float(spec["lognormal_sigma"])
    out = []
    for i in range(n):
        x = med * math.exp(sig * nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(max(round(x), spec["min"]), spec["max"])))
    return out


def popularity(stream: Dict) -> List[float]:
    k = len(stream["tenants"])
    pop = stream.get("popularity", {})
    if "zipf_s" in pop:
        w = [1.0 / (i + 1) ** float(pop["zipf_s"]) for i in range(k)]
    else:
        w = [1.0] * k
    tot = sum(w)
    return [x / tot for x in w]


def span_requests(stream: Dict, rate: float, start: float, span: float,
                  rng: np.random.Generator) -> List[Dict]:
    n = int(round(rate * span))
    if n <= 0:
        return []
    due = start + np.sort(rng.uniform(0.0, span, n))
    plens = rng.permutation(length_quantiles(stream["prompt_len"], n))
    olens = rng.permutation(length_quantiles(stream["output_len"], n))
    counts = _largest_remainder(n, popularity(stream))
    tenants = rng.permutation([t for t, c in zip(stream["tenants"], counts)
                               for _ in range(c)])
    return [dict(due=float(d), tenant=int(t), prompt_len=int(p),
                 out_len=int(o))
            for d, t, p, o in zip(due, tenants, plens, olens)]


def schedule(mix: Dict, *, seed: int, seconds: float, vocab: int,
             knee_rps: Optional[float] = None) -> List[Req]:
    """The whole run's requests, ordered by due time: the warm-up span
    ``[0, warmup_s)`` and the window ``[warmup_s, warmup_s + seconds)``."""
    rng = np.random.default_rng(seed)
    warm = float(mix["warmup_s"])
    rows: List[Dict] = []
    for stream in mix["streams"]:
        rate = stream_rate(stream, knee_rps)
        for start, span in ((0.0, warm), (warm, float(seconds))):
            rows += span_requests(stream, rate, start, span, rng)
    rows.sort(key=lambda r: (r["due"], r["tenant"]))
    reqs = []
    for i, r in enumerate(rows):
        reqs.append(Req(rid=i, prompt=rng.integers(
            0, vocab, r["prompt_len"], dtype=np.int32), **r))
    return reqs


def prompt_lengths(mix: Dict) -> List[int]:
    """Every prompt length the mix can send: the shapes set-up warms."""
    out = set()
    for s in mix["streams"]:
        spec = s["prompt_len"]
        if "values" not in spec:
            raise ValueError("prompt lengths must be a discrete set: each "
                             "length is a compiled prefill shape")
        out.update(int(v) for v in spec["values"])
    return sorted(out)


def weights(mix: Dict) -> Dict[int, float]:
    return {int(t): float(w) for t, w in mix["weights"].items()}
