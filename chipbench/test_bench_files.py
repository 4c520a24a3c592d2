"""CPU tests that BENCHMARK.json and the files it names hold together."""

import json
import re
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("m", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_names_and_units(m):
    assert NAME.match(m["name"]), m["name"]
    assert UNIT.match(m["unit"]), m["unit"]
    assert m["better"] in ("lower", "higher")


def test_every_per_layer_metric_has_a_reader_and_moves_an_e2e_metric():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert (HERE / "metrics" / f"{m['name']}.py").is_file(), m["name"]
        assert m["moves"] in e2e


@pytest.mark.parametrize("w", BENCH["workloads"],
                         ids=[w["name"] for w in BENCH["workloads"]])
def test_workload_names_existing_files(w):
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    configs = {c["name"]: c for c in BENCH["configs"]}
    assert w["config"] in configs
    assert (ROOT / configs[w["config"]]["file"]).is_file()
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    assert traffic["chips"] == w["chips"]
    limits = json.loads((HERE / "limits" / f"{w['name']}.json").read_text())
    assert set(limits) == {"loss", "grad", "v", "m_v", "dx"}


@pytest.mark.parametrize("c", BENCH["configs"],
                         ids=[c["name"] for c in BENCH["configs"]])
def test_config_file_states_its_cuts(c):
    config = json.loads((ROOT / c["file"]).read_text())
    assert config["source"] == c["source"]
    assert config["reduced"] == c["reduced"]
    for key in c["reduced"]:
        assert NAME.match(key)
        assert config[key] != config["published"][key]
