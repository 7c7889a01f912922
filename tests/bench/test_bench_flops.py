"""The dense decoder's counts (bench/arch/dense_gqa.py) against counts
worked out by hand, and the roofline (bench/flops.py)."""
import json

import pytest

from bench import flops
from bench.arch import dense_gqa as arch
from smoke import ROOT

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def model(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())["model"]


def test_internlm2_counts():
    m = model("internlm2-1.8b")
    # q 2048*16*128 + k, v 2*2048*8*128 + o 16*128*2048 + mlp 3*2048*8192
    assert arch.layer_matmul_params(m) == 62_914_560
    assert arch.head_params(m) == 189_530_112            # 92544 x 2048
    assert arch.matmul_params(m) == 1_699_479_552        # 24 layers + head
    assert arch.kv_bytes_per_token(m) == 98_304          # 96 KiB
    # (24 x (layer + 2 norms) + final norm + head) x 2 bytes
    assert arch.weight_bytes(m) == 3_399_159_808
    c = arch.decode_call(m, [0, 99])
    # 2 x 2 x params, attention 4*24*16*128 x (1 + 100) keys
    assert c["flops"] == 6_817_775_616
    # weights + 99 cached tokens read + 2 written + 2 embedding rows
    assert c["bytes"] == 3_399_159_808 + 100 * 98_304 + 98_304 + 8_192
    # 4 prompt tokens: 2*layers*4 + attention over 1+2+3+4 keys + head once
    assert arch.prefill_flops(m, 4) == 12_460_621_824


def test_granite_12l_counts():
    m = model("granite-8b-12l")
    assert m["num_layers"] == 12 and m["tie_embeddings"]
    # 4096*32*128*2 + 2*4096*8*128 + 3*4096*14336
    assert arch.layer_matmul_params(m) == 218_103_808
    assert arch.matmul_params(m) == 2_818_572_288        # + 49152 x 4096
    assert arch.kv_bytes_per_token(m) == 49_152          # 48 KiB
    c = arch.decode_call(m, [10])
    assert c["flops"] == 2 * 2_818_572_288 + 4 * 12 * 32 * 128 * 11
    assert c["bytes"] == (12 * (218_103_808 + 8192) + 4096
                          + 201_326_592) * 2 + 11 * 49_152 + 8192


def test_roofline_takes_the_larger_bound():
    m = model("internlm2-1.8b")
    c = arch.decode_call(m, [500] * 16)
    t = flops.roofline_seconds(c["flops"], c["bytes"], PEAK)
    # decode at 16 slots is bound by memory: bytes / 819 GB/s
    assert t == pytest.approx(c["bytes"] / 819e9)
    assert c["flops"] / 197e12 < t
    # a long prefill is bound by compute
    f = arch.prefill_flops(m, 1536)
    assert flops.roofline_seconds(f, arch.weight_bytes(m), PEAK) == \
        pytest.approx(f / 197e12)
