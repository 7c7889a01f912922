"""Smoke-size configuration and mix for driving the harness on the CPU."""
from __future__ import annotations

import copy
import json
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9}
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def smoke_conf(name="internlm2-1.8b"):
    """The configuration file ``name`` under the harness's checkout
    (``run.ROOT``) at its architecture's smoke widths
    (``bench/arch/<arch>.py`` ``SMOKE``), with a small engine."""
    from bench import run
    conf = json.loads((run.ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())
    conf["model"].update(run.module("arch", conf["arch"]).SMOKE)
    conf["engine"].update(batch_slots=4, max_seq=64)
    conf["knee_rps"] = 40.0
    conf["check"] = {"served_gap_max": 0.05}
    return conf


def smoke_mix(name="chat"):
    mix = json.loads((ROOT / "bench" / "traffic" / f"{name}.json")
                     .read_text())
    mix = copy.deepcopy(mix)
    mix["warmup_s"] = 0.5
    for s in mix["streams"]:
        s["prompt_len"] = {"values": [8, 16], "probs": [0.5, 0.5]} \
            if len(s["prompt_len"]["values"]) > 1 else \
            {"values": [24], "probs": [1.0]}
        if "values" in s["output_len"]:
            s["output_len"] = {"values": [4], "probs": [1.0]}
        else:
            s["output_len"] = {"lognormal_median": 16,
                               "lognormal_sigma": 0.8, "min": 4, "max": 32}
    return mix


def args(**kw):
    base = dict(workload="smoke", seed=2**31 + 5, seconds=1.5, trace=0,
                control=0, sweep="")
    base.update(kw)
    return types.SimpleNamespace(**base)


def record_ctx(monkeypatch):
    """Make ``run.run_cell`` keep each ``run.Ctx`` it builds in the list
    returned."""
    from bench import run
    kept = []

    class Ctx(run.Ctx):
        def __init__(self, **kw):
            super().__init__(**kw)
            kept.append(self)
    monkeypatch.setattr(run, "Ctx", Ctx)
    return kept
