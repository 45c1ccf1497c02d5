"""BENCHMARK.json names only what exists, and each name finds its files."""
import json
import re

import pytest

from conftest import ROOT

BM = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = BM["end_to_end"] + BM["per_layer"]


def test_top_level_keys_and_command():
    assert set(BM) == {"command", "paths", "run_seconds", "configs", "workloads",
                       "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"]
    assert BM["command"] == ["python3", "bench/run_cell.py"]
    assert 1 <= BM["run_seconds"] <= 51


@pytest.mark.parametrize("entry", BM["configs"], ids=lambda c: c["name"])
def test_config_files_state_the_sizes(entry):
    assert NAME.match(entry["name"])
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert set(entry["reduced"]) == set(cfg["reduced"])
    assert all(not k.endswith(("_dim", "_rank", "_size")) for k in entry["reduced"])


@pytest.mark.parametrize("cell", BM["workloads"], ids=lambda c: c["name"])
def test_every_cell_finds_its_files_and_metrics(cell):
    from bench import harness

    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    spec = harness.load_spec(cell["name"])
    names = {m["name"] for m in spec.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert spec.per_layer
    for m in spec.end_to_end + spec.per_layer:
        assert callable(harness.load_reader(m["name"]))


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entries(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    cells = {c["name"] for c in BM["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if metric in BM["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        assert metric["moves"] in {m["name"] for m in BM["end_to_end"]}
        assert 0 < len(metric["layer"]) <= 200
