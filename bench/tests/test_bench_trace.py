"""The trace reduction: busy union, time inside the benchmark's spans,
and the breakdown, on a hand-made trace and on one recorded on the
chip."""

import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"


def made():
    """Two chips; a window 0-100; a step 10-60 holding a decode 20-50."""
    ops = [(0, 5, "before", 0), (20, 30, "fusion.1", 0),
           (25, 40, "fusion.2", 0), (45, 55, "fusion.1", 0),
           (70, 80, "copy", 0), (20, 60, "fusion.1", 1)]
    marks = [("window", 10, 100), ("step", 10, 60), ("decode", 20, 50),
             ("prefill_wave", 62, 90)]
    return {"ops": ops, "marks": marks, "devices": 2}


def test_union_merges_overlaps():
    assert trace.union([(25, 40), (20, 30), (45, 55)]) == \
        [(20, 40), (45, 55)]


def test_busy_is_the_union_averaged_over_chips():
    td = made()
    # chip 0: 20-40, 45-55, 70-80 = 40; chip 1: 20-60 = 40; window 90
    b = trace.busy(td)
    assert b["busy_s"] == pytest.approx(40e-9)
    assert b["window_s"] == pytest.approx(90e-9)


def test_device_time_inside_a_span():
    td = made()
    # decode 20-50: chip 0 20-40 and 45-50 = 25, chip 1 30, mean 27.5
    assert trace.per_call(td, "decode") == [pytest.approx(27.5e-9)]


def test_breakdown_names_gaps_by_the_host_span():
    td = made()
    bd = trace.breakdown(td)
    names = [n for n, _ in bd["device_ops"]]
    assert names[0] == "fusion.1" and "before" not in names
    gaps = dict((n, s) for n, s in bd["idle_gaps"])
    # the union over both chips leaves 10-20 (step), 60-70 (driver),
    # 80-100 (prefill_wave to 90, then driver)
    assert bd["idle_gaps"][0][1] == pytest.approx(20e-9)
    assert set(gaps) <= {"step", "driver", "prefill_wave"}


def test_no_window_no_numbers():
    td = {"ops": [(0, 1, "x", 0)], "marks": [], "devices": 1}
    assert trace.busy(td) == {}
    assert trace.per_call(td, "decode") == []
    assert trace.breakdown(td) == {"device_ops": [], "idle_gaps": []}


RECORDED = sorted(DATA.glob("trace_*.json"))


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_recorded_chip_trace(path):
    """A trace recorded on one TPU v5e and trimmed to a few hundred ms:
    the reduction finds the chip's operations inside the benchmark's
    spans, and nothing busier than the window."""
    td = json.loads(path.read_text())
    td["ops"] = [tuple(o) for o in td["ops"]]
    td["marks"] = [tuple(m) for m in td["marks"]]
    assert td["devices"] == 1
    b = trace.busy(td)
    assert 0 < b["busy_s"] <= b["window_s"]
    calls = trace.per_call(td, "decode")
    assert calls and all(c > 0 for c in calls)
    w = trace.window(td)
    for c, (n, a, bb) in zip(calls, [m for m in td["marks"]
                                     if m[0] == "decode" and m[1] >= w[0]
                                     and m[2] <= w[1]]):
        assert c <= (bb - a) / 1e9 + 1e-12
    bd = trace.breakdown(td)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert sum(s for _, s in bd["idle_gaps"]) <= b["window_s"] - b["busy_s"] + 1e-9
