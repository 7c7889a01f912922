"""The harness end to end on the CPU, at smoke size.

The look for a chip refuses the CPU; past it, ``run_cell`` drives a whole
run (weights, warm-up, open-loop window, metrics, the reference check).
With the served path broken underneath, ``correct`` comes out false, and
so it does for the float8 control.
"""
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
