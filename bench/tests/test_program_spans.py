"""The program's spans against the device trace: the readers on a
hand-made profile and log, on a slice of a profile recorded on the chip,
and traced runs of the tiny CPU cell, in which the harness attaches the
log."""

import json
from pathlib import Path

import pytest

from bench import program_spans as ps
from bench import trace

DATA = Path(__file__).resolve().parent / "data"


def made():
    """One chip, the traced part 10-100.  A decode step 10-60, the host
    outside every step 60-62, then a step 62-98 that prefills, splices
    and decodes.  The device runs the decode 20-48, a prefill op 70-78
    and the next decode 96-100."""
    marks = [("engine.step", 10, 60), ("engine.schedule", 10, 14),
             ("engine.decode", 14, 50), ("decode.prep", 14, 18),
             ("decode.launch", 18, 20), ("decode.readback", 20, 50),
             ("engine.retire", 50, 58),
             ("engine.step", 62, 98), ("engine.schedule", 62, 63),
             ("engine.prefill", 63, 90), ("prefill.forward", 63, 80),
             ("prefill.readback", 80, 85), ("prefill.handles", 85, 90),
             ("engine.splice", 90, 95), ("engine.decode", 95, 98)]
    return {"ops": [(0, 5, "before", 0), (20, 48, "fusion.1", 0),
                    (70, 78, "while.2", 0), (96, 100, "fusion.1", 0)],
            "marks": [("window", 10, 100), ("step", 10, 60),
                      ("decode", 14, 50), ("step", 62, 98)],
            "devices": 1, "program_marks": marks,
            "modules": [("jit_paged_decode", 20, 48, 0),
                        ("jit_paged_decode", 96, 100, 0),
                        ("jit_argmax", 48, 49, 0)]}


def test_idle_time_is_put_down_to_the_innermost_program_span():
    td = made()
    got = ps.idle_by_phase(td)
    want = {"engine.schedule": 5, "decode.prep": 4, "decode.launch": 2,
            "decode.readback": 2, "engine.retire": 8, "engine.step": 2,
            "outside": 2, "prefill.forward": 9, "prefill.readback": 5,
            "prefill.handles": 5, "engine.splice": 5, "engine.decode": 1}
    assert got == {k: pytest.approx(v * 1e-9) for k, v in want.items()}
    # every idle second is counted once: the window less the busy union
    assert sum(got.values()) == pytest.approx(
        trace.busy(td)["window_s"] - trace.busy(td)["busy_s"])
    assert ps.idle_below_step_share(got) == pytest.approx(46 / 50)


def test_idle_inside_prefill_waves_and_decode_only_steps():
    td = made()
    # the wave 63-90 holds 8 busy of 27
    assert ps.prefill_idle_ms_per_wave(td) == pytest.approx(19e-6)
    # only the first step decodes without prefill or splice: 22 of 50 idle
    assert ps.decode_idle_ms_per_step(td) == pytest.approx(22e-6)


def test_program_time_is_found_by_name():
    td = made()
    assert ps.program_ms(td) == pytest.approx(16e-6)
    # a program whose decode bears another name reads nothing
    td["modules"] = [("jit_step",) + m[1:] for m in td["modules"]]
    assert ps.program_ms(td) is None


def test_no_traced_part_no_device_readings():
    td = made()
    td["marks"] = [m for m in td["marks"] if m[0] != "window"]
    assert ps.idle_by_phase(td) == {}
    assert ps.prefill_idle_ms_per_wave(td) is None
    assert ps.decode_idle_ms_per_step(td) is None
    assert ps.program_ms(td) is None


def reader(name):
    from bench import harness
    return harness.Layout(Path(__file__).resolve().parents[2]).metric(name)


def test_the_reader_finds_no_profile_of_this_run():
    """``decode_program_ms`` reads the programs of the profile the harness
    loaded, and nothing from one that holds none (a profile with no TPU:
    no modules, no decode calls), silently."""
    td = made()
    td.update(ops=[], modules=[])
    assert reader("decode_program_ms").read("decode_program_ms",
                                            {"trace": td}) is None


def test_the_reader_says_when_decode_calls_have_no_program(capsys):
    """Decode calls in the traced part but no ``jit_paged_decode``
    program: the reader reads nothing and says so on stderr; with the
    program there it reads it and says nothing."""
    td = made()
    read = reader("decode_program_ms").read
    assert read("decode_program_ms", {"trace": td}) == pytest.approx(16e-6)
    assert capsys.readouterr().err == ""
    td["modules"] = [("jit_step",) + m[1:] for m in td["modules"]]
    assert read("decode_program_ms", {"trace": td}) is None
    assert "no jit_paged_decode program" in capsys.readouterr().err


@pytest.mark.parametrize("name,want", [
    ("prefill_idle_ms_per_wave", 19e-6), ("decode_idle_ms_per_step", 22e-6),
    ("decode_program_ms", 16e-6)])
def test_readers_of_the_profile_read_the_harness_trace(name, want):
    """The per-layer metrics of ``BENCHMARK.json`` that read the
    program's spans in the profile take them from ``ctx["trace"]``."""
    assert reader(name).read(name, {"trace": made()}) == \
        pytest.approx(want)


@pytest.mark.parametrize("name,want", [
    ("sched_ms_per_step", 85.0), ("prefill_cache_loads_per_wave", 3.0)])
def test_readers_of_the_log_read_the_harness_records(name, want):
    ctx = {"program_spans": records(), "t0": 1.0, "t1": 3.0}
    assert reader(name).read(name, ctx) == pytest.approx(want)
    # an untraced run's ctx holds no records: nothing to read
    assert reader(name).read(name, dict(ctx, program_spans=[])) is None


def records():
    """A log over the window 1.0-3.0 s: a step before it, a slow step
    that prefills (the cache loads and compiles counted on its spans), a
    short one that parks a request, and one after the window."""
    return [
        ("engine.step", 0.5, 0.9, None, {}),
        ("engine.schedule", 0.5, 0.6, 0, {}),
        ("engine.step", 1.0, 2.5, None, {"step": 1, "live": 1}),
        ("engine.schedule", 1.0, 1.1, 2, {"rids": [1]}),
        ("engine.prefill", 1.1, 2.0, 2, {"rids": [1], "n": 1}),
        ("prefill.forward", 1.1, 1.8, 4, {"cache_loads": 2, "compiles": 1}),
        ("prefill.readback", 1.8, 2.0, 4, {}),
        ("engine.decode", 2.0, 2.4, 2, {"rids": [1]}),
        ("decode.launch", 2.1, 2.2, 7, {"compiles": 1}),
        ("engine.schedule", 2.4, 2.45, 2, {}),
        ("engine.step", 2.6, 2.7, None, {"step": 2, "live": 1}),
        ("engine.schedule", 2.6, 2.62, 10, {"parked": [1]}),
        ("engine.extract", 2.61, 2.62, 11, {"rid": 1}),
        ("engine.step", 3.0, 3.1, None, {}),
    ]


def test_host_readings_over_the_window():
    rec = records()
    assert ps.sched_ms_per_step(rec, 1.0, 3.0) == pytest.approx(85.0)
    assert ps.prefill_cache_loads_per_wave(rec, 1.0, 3.0) == 3.0
    assert ps.sched_ms_per_step(rec, 5.0, 6.0) is None
    assert ps.prefill_cache_loads_per_wave(rec, 2.6, 3.0) is None
    ph = ps.by_phase(rec, 1.0, 3.0)
    # the slow step less its phases 0.05 s, the short one 0.08 s: per step
    assert ph["engine.step"]["self_ms"] == pytest.approx(65.0)
    assert ph["engine.schedule"]["self_ms"] == pytest.approx(80.0)
    assert ph["prefill.forward"] == {"self_ms": pytest.approx(350.0),
                                     "compiles": 1, "cache_loads": 2}
    assert ph["decode.launch"]["compiles"] == 1


def test_slow_steps_carry_their_spans():
    slow = ps.slow_steps(records(), 1.0, 3.0)
    assert len(slow) == 1
    assert slow[0]["at_s"] == pytest.approx(0.0)
    assert slow[0]["ms"] == pytest.approx(1500.0)
    names = [s[0] for s in slow[0]["spans"]]
    assert names == ["engine.step", "engine.schedule", "engine.prefill",
                     "prefill.forward", "prefill.readback", "engine.decode",
                     "decode.launch", "engine.schedule"]
    assert slow[0]["spans"][3][1:3] == [pytest.approx(100.0),
                                        pytest.approx(700.0)]


RECORDED = sorted(DATA.glob("program_spans_*.json"))


@pytest.mark.parametrize("path", RECORDED, ids=lambda p: p.name)
def test_recorded_chip_profile(path):
    """A profile recorded on one TPU v5e with the span log attached,
    trimmed to one prefill wave and the decode steps after it."""
    d = json.loads(path.read_text())
    td = {k: [tuple(x) for x in v] if isinstance(v, list) else v
          for k, v in d.items()}
    b = trace.busy(td)
    idle = ps.idle_by_phase(td)
    assert sum(idle.values()) == pytest.approx(b["window_s"] - b["busy_s"])
    assert ps.idle_below_step_share(idle) >= 0.9
    assert ps.prefill_idle_ms_per_wave(td) > 0
    assert ps.decode_idle_ms_per_step(td) > 0
    # the decode found by its name takes the device time the benchmark's
    # span around the backend call holds, less the argmax and uploads
    by_name = ps.program_ms(td)
    by_span = [c * 1e3 for c in trace.per_call(td, "decode") if c > 0]
    assert by_name and by_span
    assert by_name == pytest.approx(sum(by_span) / len(by_span), rel=0.05)
    assert by_name <= max(by_span)


def test_tiny_cell_with_the_log_attached(checkout, compile_cache, capsys):
    """The run prints its own result line, with the four readers of the
    span log the harness attached, then the program's spans: the
    scheduler's host time per step and the prefill's cache loads."""
    args = ["--workload", "tiny.chat", "--seed", str(2**31 + 5),
            "--seconds", "6", "--trace", "1"]
    rc = ps.main(args, root=checkout, require_tpu=False, cache=compile_cache)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    res, got = json.loads(out[-2]), json.loads(out[-1])["program_spans"]
    assert res["correct"] is True and "host_ms_per_step" in res["metrics"]
    # no TPU here: the readers of device time find no operations
    assert "prefill_idle_ms_per_wave" not in res["metrics"]
    assert "decode_idle_ms_per_step" not in res["metrics"]
    assert res["metrics"]["sched_ms_per_step"]["value"] == \
        got["sched_ms_per_step"]
    assert res["metrics"]["prefill_cache_loads_per_wave"]["value"] == \
        got["prefill_cache_loads_per_wave"]
    assert got["steps"] > 0
    assert got["sched_ms_per_step"] > 0
    assert got["prefill_cache_loads_per_wave"] is not None
    for phase in ("engine.step", "engine.schedule", "engine.prefill",
                  "engine.splice", "engine.decode", "engine.retire",
                  "prefill.forward", "decode.launch", "splice.page_in"):
        assert phase in got["by_phase"], phase
    assert got["slow_steps"] == [] or all(
        s["ms"] > 1000 for s in got["slow_steps"])
