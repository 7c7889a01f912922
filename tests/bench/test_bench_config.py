"""The program's ModelConfig from a configuration file's ``model``
(bench/client.py ``program_config``)."""
import dataclasses
import json

import pytest

from bench.client import program_config
from smoke import ROOT


@pytest.mark.parametrize("name", ["internlm2-1.8b", "granite-8b-12l"])
def test_each_file_applies_its_sizes(name):
    m = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                   .read_text())["model"]
    cfg = program_config(m)
    for k, v in m.items():
        if k != "program_name":
            assert getattr(cfg, k) == v, k


def test_a_nested_moe_dict_keeps_the_other_fields():
    from repro.configs import get_config
    base = get_config("deepseek-v2-236b")
    cfg = program_config({"program_name": "deepseek-v2-236b",
                          "num_layers": 9, "moe": {"num_experts": 10}})
    assert cfg.num_layers == 9 and cfg.moe.num_experts == 10
    assert cfg.moe == dataclasses.replace(base.moe, num_experts=10)
    assert cfg.moe.top_k == 6 and cfg.moe.num_shared_experts == 2
    assert cfg.dense_layer_prefix == 1 and cfg.mla == base.mla


def test_a_nested_ssm_dict():
    from repro.configs import get_config
    base = get_config("mamba2-370m")
    cfg = program_config({"program_name": "mamba2-370m",
                          "ssm": {"chunk": 64}})
    assert cfg.ssm == dataclasses.replace(base.ssm, chunk=64)


@pytest.mark.parametrize("model,key", [
    ({"q_lora_rank": 1536}, "q_lora_rank"),
    ({"moe": {"n_group": 8}}, "moe.n_group"),
    ({"mla": {"q_lora_rank": 1536}}, "mla"),
])
def test_a_key_the_program_cannot_take_raises(model, key):
    with pytest.raises(ValueError, match=key.replace(".", r"\.")):
        program_config(dict(model, program_name="deepseek-v2-236b"))
