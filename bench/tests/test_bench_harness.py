"""The harness: it refuses to run off the TPU, finds what a cell names by
its name, and its check fails a served path broken underneath."""

import json

import numpy as np
import pytest

from bench import harness

ARGS = ["--workload", "tiny.chat", "--seed", str(2**31 + 3),
        "--seconds", "8"]


def run(checkout, cache, capsys, *extra, fault=None):
    rc = harness.main(ARGS + list(extra), root=checkout, require_tpu=False,
                      cache=cache, fault=fault)
    assert rc == 0
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


def test_refuses_without_a_tpu(checkout, capsys):
    with pytest.raises(SystemExit) as e:
        harness.main(ARGS + ["--trace", "0"], root=checkout)
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_refuses_without_the_program(checkout, capsys):
    (checkout / "src").unlink()
    with pytest.raises(SystemExit) as e:
        harness.main(ARGS, root=checkout, require_tpu=False, cache=False)
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


def test_a_new_config_traffic_and_metric_are_found_by_name(checkout,
                                                         compile_cache,
                                                         capsys):
    """Files and entries alone add a configuration, a mix and a metric."""
    (checkout / "bench/metrics/throwaway.py").write_text(
        "def read(name, ctx):\n"
        "    if name.endswith('.window_s'):\n"
        "        return ctx['t1'] - ctx['t0']\n"
        "    if name.endswith('.traced_s'):\n"
        "        return ctx['t1'] - ctx['trace_t0']\n"
        "    return float(ctx['counters']['steps'] + 1)\n")
    spec = json.loads((checkout / "BENCHMARK.json").read_text())
    for m in ("steps", "window_s", "traced_s"):
        spec["per_layer"].append({
            "name": "throwaway." + m, "unit": "count", "better": "lower",
            "source": "program_counter", "layer": "engine host step",
            "moves": "itl_p99_ms", "workloads": ["tiny.chat"]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(spec))
    res = run(checkout, compile_cache, capsys, "--trace", "1")
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["throwaway.steps"] >= 1
    # host spans and records are read over the whole window, the device
    # trace over its traced part (the tiny mix's trace_s)
    assert got["throwaway.window_s"] == pytest.approx(8.0)
    assert got["throwaway.traced_s"] == pytest.approx(1.0)
    assert "host_ms_per_step" in res["metrics"]
    assert list(res)[-1] == "checks"
    assert res["device"]["platform"] == "cpu"


def test_untraced_run_reports_the_cells_end_to_end_metrics(checkout,
                                                           compile_cache,
                                                           capsys):
    rec = checkout / "records.json"
    res = run(checkout, compile_cache, capsys, "--trace", "0",
              "--records", str(rec))
    assert res["correct"] is True, res
    assert set(res["metrics"]) == {"setup_s", "ttft_p50_ms", "itl_p99_ms"}
    assert res["attempted"] > 0 and res["failed"] == 0
    # --records lists every request due in the window and every step
    got = json.loads(rec.read_text())
    assert len(got["requests"]) == res["attempted"]
    assert got["steps"] and all(len(s) == 5 for s in got["steps"])
    assert res["checks"]["logit_gap"]["value"] <= \
        res["checks"]["logit_gap"]["limit"]


def test_the_control_in_the_programs_place_is_not_correct(checkout,
                                                        compile_cache,
                                                        capsys):
    """The float8 control goes through the same comparison as the
    program and fails it."""
    res = run(checkout, compile_cache, capsys, "--trace", "0",
              "--control", "1")
    assert res["correct"] is False, res
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


def alter_tokens(eng):
    """A token altered where it is produced: every decoded token."""
    inner = eng.backend.decode

    def decode(tokens, states):
        nxt, states = inner(tokens, states)
        return (np.asarray(nxt) + 1) % 256, states

    eng.backend.decode = decode


def state_unchanged(eng):
    """A decode step that hands back the state it was given."""
    inner = eng.backend.decode

    def decode(tokens, shard):
        before = shard.states
        nxt, shard = inner(tokens, shard)
        shard.states = before
        return nxt, shard

    eng.backend.decode = decode


@pytest.mark.parametrize("fault", [alter_tokens, state_unchanged],
                         ids=lambda f: f.__name__)
def test_a_broken_served_path_is_not_correct(checkout, compile_cache,
                                             capsys, fault):
    res = run(checkout, compile_cache, capsys, "--trace", "0",
              fault=fault)
    assert res["correct"] is False, res
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


def test_admission_groups_follow_the_schedule():
    """Requests due during a long admitting step are admitted together in
    the next one; each group yields its runs of two or more in a row."""
    from bench.generators.open_loop import Req

    def req(due, length):
        return Req(due, np.ones(length, np.int32), 50)

    admit_s = {128: 0.3, 512: 1.0}
    reqs = [req(0.0, 512), req(0.2, 128), req(0.5, 512), req(0.9, 128),
            req(5.0, 128)]
    groups = harness.admission_groups(reqs, admit_s, 0.03, 8, [1.0])
    assert groups == [(128, 512), (128, 512, 128), (512, 128)]
    # at half the pace the last of the three falls due after the step
    assert harness.admission_groups(reqs, admit_s, 0.03, 8, [0.5]) == \
        [(128, 512)]
    # one slot: nothing is ever admitted together
    assert harness.admission_groups(reqs, admit_s, 0.03, 1, [1.0, 2.0]) \
        == []
    # ... but neighbours due close together are warmed all the same
    assert harness.admission_groups(reqs, admit_s, 0.03, 1, [1.0],
                                    pairs_s=0.35) == [(128, 512), (512, 128)]
