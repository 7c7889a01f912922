"""BENCHMARK.json is well formed, and every name in it is found as a file."""
import json
import re

import pytest

from smoke import ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_each_cell_finds_its_files(cell):
    confs = {c["name"]: c for c in SPEC["configs"]}
    conf_entry = confs[cell["config"]]
    conf = json.loads((ROOT / conf_entry["file"]).read_text())
    assert conf["name"] == cell["config"]
    assert sorted(conf["reduced"]) == sorted(conf_entry["reduced"])
    mix = json.loads((ROOT / "bench" / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    assert mix["streams"]
    assert (ROOT / "bench" / "reference" / f"{conf['reference']}.py").exists()
    assert cell["chips"] in (1, 4)
    e2e = [m["name"] for m in SPEC["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    per = [m for m in SPEC["per_layer"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    assert "setup_s" in e2e and len(e2e) >= 2 and per
    for m in per:
        assert m["moves"] in e2e


@pytest.mark.parametrize("path", sorted((ROOT / "bench" / "configs")
                                        .glob("*.json")),
                         ids=lambda p: p.stem)
def test_each_configuration_file_is_complete(path):
    # a file may wait for its cell (PERF.md, Open questions): a later PR
    # then adds only BENCHMARK.json entries
    from bench import run
    conf = json.loads(path.read_text())
    assert conf["name"] == path.stem and NAME.match(conf["name"])
    assert (ROOT / "bench" / "reference" / f"{conf['reference']}.py").exists()
    assert conf["knee_rps"] > 0 and 0 < conf["check"]["served_gap_max"]
    # the architecture module lays out the file's model, and counts it
    arch = run.module("arch", conf["arch"])
    m = conf["model"]
    assert arch.layout(m) and all(len(t) == 3 for t in arch.layout(m))
    assert arch.prefill_flops(m, 8) > 0
    assert arch.decode_call(m, [0, 7])["bytes"] > 0
    assert set(arch.SMOKE) <= set(m)
    for key, change in conf["reduced"].items():
        assert NAME.match(key) and change["published"] != change["here"]


def test_every_metric_has_a_reader():
    from bench import run
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert callable(run.reader(m["name"]))


def test_every_per_layer_metric_names_its_cells():
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert m["workloads"] and set(m["workloads"]) <= cells


def test_names_units_and_bounds():
    names = [c["name"] for c in SPEC["configs"]] + \
        [w["name"] for w in SPEC["workloads"]] + \
        [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]] + \
        [w["traffic"] for w in SPEC["workloads"]] + \
        [k for c in SPEC["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["layer"] for m in SPEC["per_layer"]}
    assert all("\n" not in x and len(x) <= 200 for x in layers)
    for m in SPEC["per_layer"]:
        if "_roofline" in m["name"]:
            assert m["unit"] == "%"


def test_at_most_half_the_cells_on_four_chips():
    four = sum(1 for w in SPEC["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(SPEC["workloads"]) // 2)
