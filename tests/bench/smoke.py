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
    conf = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())
    conf["model"].update(num_layers=2, d_model=64, num_heads=4,
                         num_kv_heads=2, head_dim=16, d_ff=128,
                         vocab_size=256)
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
