"""BENCHMARK.json against the contract the harness reads it by: names
and units in their alphabets, every file a cell needs under bench/, and a
reader for every per-layer metric."""
import json
import re

import pytest

from bench.run import BENCH, ROOT, load_json

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("key", ["configs", "workloads", "end_to_end",
                                 "per_layer"])
def test_names_unique(key):
    names = [e["name"] for e in SPEC[key]]
    assert len(names) == len(set(names))


def test_config_traffic_pairs_unique():
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("m", METRICS, ids=[m["name"] for m in METRICS])
def test_metric(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    if m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        cells = {w["name"] for w in SPEC["workloads"]}
        assert set(m.get("workloads", cells)) <= cells


@pytest.mark.parametrize("w", SPEC["workloads"],
                         ids=[w["name"] for w in SPEC["workloads"]])
def test_cell_files(w):
    configs = {c["name"]: c for c in SPEC["configs"]}
    cfg = load_json(ROOT / configs[w["config"]]["file"])
    assert cfg["name"] == w["config"]
    assert (BENCH / "operators" / f"{cfg['family']}.py").is_file()
    mix = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    assert mix["name"] == w["traffic"]
    assert set(cfg["limits"]) >= {"hist_gap", "x_gap", "resid"}
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200
