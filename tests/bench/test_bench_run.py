"""The harness end to end on the CPU, at smoke size.

The look for a chip refuses the CPU; past it, ``run_cell`` drives a whole
run (weights, warm-up, open-loop window, metrics, the reference check).
With the served path broken underneath, ``correct`` comes out false, and
so it does for the float8 control.
"""
import json
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import pytest

import smoke
from bench import run

CELL = "internlm2-1.8b.chat"


def _run(cell=CELL, mix="chat", **kw):
    _, c, conf, _, e2e, per = run.load_cell(cell)
    return run.run_cell(smoke.args(**kw), c, smoke.smoke_conf(),
                        smoke.smoke_mix(mix), e2e, per, smoke.PEAK,
                        smoke.DEVICE, lambda m: None)


def _cpu_run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed",
         "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _cpu_run(smoke.ROOT)
    assert p.returncode != 0
    assert "device: platform=cpu" in p.stdout
    assert '"correct"' not in p.stdout


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(smoke.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(smoke.ROOT / "bench", tmp_path / "bench")
    p = _cpu_run(tmp_path)
    assert p.returncode != 0 and '"correct"' not in p.stdout


def test_smoke_run_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p80_s", "itl_p99_ms", "setup_s"}
    assert list(out)[-1] == "checks"


def test_traced_noisy_neighbour_reports_host_layers():
    out = _run("internlm2-1.8b.noisy-neighbour", "noisy-neighbour", trace=1)
    assert out["correct"], out["checks"]
    # no device plane on the CPU: the device readers stay silent
    assert {"queue_wait_p95_s.light", "ttft_p95_s.light",
            "control_tick_ms.noisy", "step_ms.noisy"} <= set(out["metrics"])
    assert "decode_roofline.noisy" not in out["metrics"]
    assert "busy_s" in out["device"] and "breakdown" in out


def _state_unchanged(orig):
    def f(params, caches, *a, **k):
        logits, _ = orig(params, caches, *a, **k)
        return logits, caches
    return f


def _half_batch_left_out(orig):
    def f(*a, **k):
        logits, caches = orig(*a, **k)
        # every other slot: the first free slot is the lowest, so the
        # upper half of the batch is rarely busy at smoke size
        return logits.at[1::2].set(0.0), caches
    return f


def _token_altered(orig):
    def f(*a, **k):
        logits, caches = orig(*a, **k)
        return jnp.roll(logits, 1, axis=-1), caches
    return f


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch_left_out,
                                   _token_altered],
                         ids=["state-unchanged", "half-batch-left-out",
                              "token-altered"])
def test_a_broken_served_path_is_not_correct(monkeypatch, fault):
    import repro.serve.engine as engine
    monkeypatch.setattr(engine, "forward_decode",
                        fault(engine.forward_decode))
    out = _run()
    assert not out["correct"]
    assert out["checks"]["served_gap_max"]["value"] > \
        out["checks"]["served_gap_max"]["limit"]


def test_the_float8_control_is_not_correct():
    out = _run(control=1)
    assert not out["correct"]
    assert out["failed"] > 0
    c = out["checks"]
    assert c["control_gap_max"]["value"] > c["control_gap_max"]["limit"]
    # the program's own tokens, in the same run, are within the limit
    assert c["served_gap_max"]["value"] <= c["served_gap_max"]["limit"]


def test_a_new_architecture_needs_only_new_files(tmp_path, monkeypatch):
    """In a copy of the benchmark's files, a configuration of a new
    architecture name comes with new files alone: its architecture module,
    its configuration file and the cell's entries. The harness, pointed at
    the copy, runs it and finds it correct."""
    shutil.copy(smoke.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(smoke.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    (tmp_path / "bench" / "arch" / "twin_gqa.py").write_text(
        '"""A second architecture name over the dense decoder."""\n'
        "from bench.arch.dense_gqa import (  # noqa: F401\n"
        "    SMOKE, decode_call, layout, prefill_flops, program_params)\n")
    conf = json.loads((smoke.ROOT / "bench" / "configs"
                       / "internlm2-1.8b.json").read_text())
    conf.update(name="twin-1.8b", arch="twin_gqa")
    (tmp_path / "bench" / "configs" / "twin-1.8b.json").write_text(
        json.dumps(conf))
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][0], name="twin-1.8b",
                                file="bench/configs/twin-1.8b.json"))
    spec["workloads"].append(dict(spec["workloads"][0], name="twin-1.8b.chat",
                                  config="twin-1.8b"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("twin-1.8b.chat")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    # nothing the benchmark had is changed
    assert all(p.read_bytes() == b for p, b in before.items())

    monkeypatch.setattr(run, "ROOT", tmp_path)
    ctxs = smoke.record_ctx(monkeypatch)
    _, c, _, _, e2e, per = run.load_cell("twin-1.8b.chat")
    out = run.run_cell(smoke.args(), c, smoke.smoke_conf("twin-1.8b"),
                       smoke.smoke_mix("chat"), e2e, per, smoke.PEAK,
                       smoke.DEVICE, lambda m: None)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"ttft_p80_s", "itl_p99_ms", "setup_s"}
    arch = ctxs[0].arch
    assert arch.__file__ == str(tmp_path / "bench" / "arch" / "twin_gqa.py")
    assert arch is run.module("arch", "twin_gqa")
    assert ctxs[0].model["num_layers"] == arch.SMOKE["num_layers"]
