#!/usr/bin/env python3
"""Gate bench --json results against committed thresholds.

    python tools/check_bench.py RESULTS.json [MORE.json ...] \\
        benchmarks/bench_thresholds.json

The LAST argument is the thresholds file; every earlier argument is a
results document (bench_fairness, ...). Several
results files are merged — metric maps unioned (a duplicate metric name
across files is an error: two benches must not claim the same row), and
the overall ``ok`` flag is the AND across files — so one shared
thresholds file gates the whole suite.

Thresholds map metric names (the bench's "section,metric" row names) to
{"min": x} / {"max": x} bounds (inclusive). A metric missing from the
results is a failure too — a silently dropped bench must not pass the
gate. Keys starting with "_" are comments. Stdlib only, exit 1 with a
listing on any violation.
"""
from __future__ import annotations

import json
import pathlib
import sys


def merge_results(docs: list) -> dict:
    """Union several bench --json docs into one checkable document."""
    merged = {"ok": True, "metrics": {}}
    for doc in docs:
        merged["ok"] = merged["ok"] and bool(doc.get("ok", False))
        for name, v in doc.get("metrics", {}).items():
            if name in merged["metrics"]:
                raise ValueError(f"metric {name!r} appears in more than "
                                 f"one results file")
            merged["metrics"][name] = v
    return merged


def check(results: dict, thresholds: dict) -> list:
    metrics = results.get("metrics", {})
    problems = []
    for name, bound in thresholds.items():
        if name.startswith("_"):
            continue
        if name not in metrics:
            problems.append(f"{name}: missing from results")
            continue
        v = metrics[name]
        if "min" in bound and v < bound["min"]:
            problems.append(f"{name}: {v:.4f} < min {bound['min']}")
        if "max" in bound and v > bound["max"]:
            problems.append(f"{name}: {v:.4f} > max {bound['max']}")
    if not results.get("ok", False):
        problems.append("bench reported ok=false (a claim failed)")
    return problems


def main(argv) -> int:
    if len(argv) < 2:
        print(__doc__.strip())
        return 2
    docs = [json.loads(pathlib.Path(p).read_text()) for p in argv[:-1]]
    thresholds = json.loads(pathlib.Path(argv[-1]).read_text())
    try:
        results = merge_results(docs)
    except ValueError as e:
        print(f"bad results set: {e}")
        return 2
    problems = check(results, thresholds)
    if problems:
        print("bench regression vs committed thresholds:")
        for p in problems:
            print(f"  {p}")
        return 1
    n = sum(1 for k in thresholds if not k.startswith("_"))
    print(f"all {n} thresholds hold")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
