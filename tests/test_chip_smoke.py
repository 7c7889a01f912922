"""``chip_smoke.py`` refuses to run without a TPU, and the compile cache
goes where it is told.

Both run in child processes on the CPU: the smoke script must exit
non-zero there and print no success line, and the cache directory is read
from the environment when JAX starts, so each case needs a fresh process.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent


def _run(args, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=str(ROOT / "src"))
    full.pop("JAX_COMPILATION_CACHE_DIR", None)
    full.update(env)
    return subprocess.run([sys.executable, *args], env=full, cwd=ROOT,
                          capture_output=True, text=True, timeout=300)


def test_chip_smoke_exits_nonzero_without_a_tpu():
    p = _run([str(ROOT / "chip_smoke.py")])
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout
    assert "platform=cpu" in p.stdout


@pytest.mark.parametrize("from_env", [False, True])
def test_compile_cache_directory(tmp_path, from_env):
    code = ("from repro.launch.compile_cache import configure_compile_cache"
            " as c; print(c()); print(c())")
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)} if from_env else {}
    p = _run(["-c", code], **env)
    assert p.returncode == 0, p.stderr
    want = str(tmp_path) if from_env else str(ROOT / ".jax_cache")
    assert p.stdout.split() == [want, want]
