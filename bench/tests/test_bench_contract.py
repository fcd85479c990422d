"""BENCHMARK.json names only what exists, in the shape the harness reads:
every cell's configuration, traffic, generator, reference and per-layer
reader is a file found by its name."""

import json
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [c["name"] for c in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_are_unique_and_well_formed():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in SPEC[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=CELLS)
def test_cell_files_exist(cell):
    conf = next(c for c in SPEC["configs"] if c["name"] == cell["config"])
    cfile = json.loads((REPO / conf["file"]).read_text())
    assert cfile["reduced"] == conf["reduced"]
    assert (REPO / "bench/reference" / f"{cfile['reference']}.py").is_file()
    traffic = json.loads(
        (REPO / "bench/traffic" / f"{cell['traffic']}.json").read_text())
    assert (REPO / "bench/generators"
            / f"{traffic['process']}.py").is_file()
    assert cell["chips"] == 1 and len(cell["why"]) <= 200


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_enough(cell):
    def of(kind):
        return [m["name"] for m in SPEC[kind]
                if "workloads" not in m or cell in m["workloads"]]
    e2e = of("end_to_end")
    assert "setup_s" in e2e and len(e2e) >= 2
    assert of("per_layer")


@pytest.mark.parametrize("m", SPEC["per_layer"],
                         ids=[m["name"] for m in SPEC["per_layer"]])
def test_per_layer_metric_has_a_reader_and_moves_a_reported_metric(m):
    reader = REPO / "bench/metrics" / f"{m['name'].split('.')[0]}.py"
    assert reader.is_file()
    moved = next(e for e in SPEC["end_to_end"] if e["name"] == m["moves"])
    for cell in m["workloads"]:
        assert cell in CELLS
        assert "workloads" not in moved or cell in moved["workloads"]
    assert "\n" not in m["layer"] and len(m["layer"]) <= 200


def test_bounds():
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
