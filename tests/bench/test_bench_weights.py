"""The weights drawn from the seed (bench/weights.py over the dense
decoder's layout, bench/arch/dense_gqa.py)."""
import hashlib

import jax
import numpy as np

import smoke
from bench import run, weights

# sha256 of the smoke-width internlm2-1.8b weights from seed 2**31 + 5 as
# drawn before the layout moved into bench/arch/ (every leaf in tree
# order: its shape and dtype, then its bytes)
SMOKE_DIGEST = \
    "b1902dc272d21df98e95c2c4bbcace447b5c3901072fd86c2560521979d4c472"


def digest(w) -> str:
    h = hashlib.sha256()
    for x in jax.tree.leaves(w):
        a = np.asarray(x)
        h.update(repr((a.shape, str(a.dtype))).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_dense_weights_are_bit_identical_to_the_first_layout():
    m = smoke.smoke_conf()["model"]
    arch = run.module("arch", "dense_gqa")
    w = weights.make_weights(arch.layout(m), m["param_dtype"], 2**31 + 5)
    assert digest(w) == SMOKE_DIGEST
    assert sorted(w) == ["embed", "final_norm", "head", "layers"]
    assert w["layers"]["wq"].dtype == np.dtype("bfloat16")
    assert np.all(np.asarray(w["layers"]["ln1"]) == 1)


def test_program_params_share_the_arrays():
    conf = smoke.smoke_conf()
    m = conf["model"]
    arch = run.module("arch", conf["arch"])
    w = weights.make_weights(arch.layout(m), m["param_dtype"], 3)
    p = arch.program_params(w, m)
    seg, = p["segments"]
    assert p["embed"]["tokens"] is w["embed"]
    assert p["embed"]["head"] is w["head"]
    assert seg["attn"]["wq"] is w["layers"]["wq"]
    assert seg["mlp"]["w_out"] is w["layers"]["w_out"]
    assert len(jax.tree.leaves(p)) == len(jax.tree.leaves(w))
