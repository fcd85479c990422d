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
    if "count" in cfile:
        assert (REPO / "bench/counts" / f"{cfile['count']}.py").is_file()
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200


def four_chip_cells_allowed(cells: list) -> bool:
    """At most half the cells, rounded down, ask for four chips; one
    always may."""
    four = sum(1 for c in cells if c["chips"] == 4)
    return four <= max(1, len(cells) // 2)


def test_four_chip_cells_are_at_most_half():
    assert four_chip_cells_allowed(SPEC["workloads"])
    one, four = {"chips": 1}, {"chips": 4}
    assert four_chip_cells_allowed([four])
    assert four_chip_cells_allowed([four, one])
    assert not four_chip_cells_allowed([four, four, one])
    assert four_chip_cells_allowed([four, four, one, one])


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


def test_a_whole_prefill_wave_lies_in_every_traced_part():
    """``prefill_idle_ms_per_wave`` reads the waves that lie wholly in the
    traced part, the window's last ``trace_s`` seconds: every seed's
    order of the window's gaps has to put an arrival there at least
    ``TAIL`` seconds before the window ends (a wave waits out a decode
    step, then prefills a prompt of up to 1536 tokens in some 150 ms).
    Arrivals sit mid-gap, so counting back from the window's end the
    first arrival at least ``TAIL`` back lies within ``2 * TAIL`` plus
    half the longest gap."""
    from bench.generators import open_loop
    TAIL = 0.3
    m = next(m for m in SPEC["per_layer"]
             if m["name"] == "prefill_idle_ms_per_wave")
    for name in m["workloads"]:
        cell = next(c for c in SPEC["workloads"] if c["name"] == name)
        traffic = json.loads((REPO / "bench/traffic"
                              / f"{cell['traffic']}.json").read_text())
        ramp, run = float(traffic["ramp_s"]), SPEC["run_seconds"]
        trace_s = min(run, float(traffic.get("trace_s", run)))
        dues = [r.due - ramp for r in open_loop.generate(
            traffic, 1, 256, ramp + run) if r.due >= ramp]
        gaps, back = [], 0.0
        for d in dues:                       # d = back + g / 2
            gaps.append(2.0 * (d - back))
            back = d + gaps[-1] / 2.0
        assert back == pytest.approx(run)
        worst = 2.0 * TAIL + max(gaps) / 2.0
        assert worst <= trace_s, (name, worst, trace_s)
