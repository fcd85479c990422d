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


@pytest.mark.parametrize("traced", [0, 1])
def test_the_span_log_is_attached_only_in_traced_runs(checkout, compile_cache,
                                                      capsys, traced):
    """An untraced run's window steps run with no span log attached, so
    its end-to-end path is the program's detached one; a traced run's
    have the harness's log, which is detached again after the window."""
    from repro.core import trace as program_trace
    seen = []

    def watch(eng):
        inner = eng.step

        def step():
            seen.append(program_trace._log)
            return inner()
        eng.step = step

    res = run(checkout, compile_cache, capsys, "--trace", str(traced),
              "--check", "0", fault=watch)
    assert seen
    if traced:
        assert all(isinstance(x, program_trace.SpanLog) for x in seen)
        assert len({id(x) for x in seen}) == 1
        assert "sched_ms_per_step" in res["metrics"]
    else:
        assert all(x is None for x in seen)
    assert program_trace._log is None


def cpu_peaks(checkout):
    """The checkout's peaks with an entry for this CPU, so that the
    decode readers have something to divide by in a test."""
    import jax
    path = checkout / "bench/peaks.json"
    peaks = json.loads(path.read_text())
    peaks["devices"][jax.devices()[0].device_kind] = {
        "bf16_flops": 1e12, "hbm_bytes_per_s": 1e9, "hbm_bytes": 1e9}
    path.write_text(json.dumps(peaks))


def fake_decodes(monkeypatch):
    """Two traced decode calls of 2 ms, of two and three live sequences:
    a CPU run's profile holds no TPU operations to pair with its spans."""
    from bench import metrics_common
    monkeypatch.setattr(metrics_common, "traced_decodes",
                        lambda ctx: [([5, 7], 2e-3), ([5, 7, 9], 2e-3)])


def test_a_cut_config_its_count_and_a_span_reader_join_by_files_alone(
        checkout, compile_cache, capsys, monkeypatch):
    """A configuration cut in depth, with a count of its own, and a
    reader of a counter on the program's spans: new files and entries
    under the checkout, and the harness as it is."""
    conf = json.loads((checkout / "bench/configs/tiny-llama.json")
                      .read_text())
    conf.update(name="tiny-cut", reduced=["n_layers"], cut={"n_layers": 4},
                count="tiny_count",
                deployment={"chips_per_layer": 1, "how": "two stages"})
    (checkout / "bench/configs/tiny-cut.json").write_text(json.dumps(conf))
    (checkout / "bench/counts").mkdir(exist_ok=True)
    (checkout / "bench/counts/tiny_count.py").write_text(
        "def decode_flops(cfg, ctx):\n"
        "    return 1000 * len(ctx)\n"
        "def decode_bytes(cfg, ctx):\n"
        "    return 500000 * len(ctx)\n")
    (checkout / "bench/metrics/prefilled.py").write_text(
        "def read(name, ctx):\n"
        "    return float(sum(info['n'] for n, a, _, _, info\n"
        "                     in ctx['program_spans']\n"
        "                     if n == 'engine.prefill'\n"
        "                     and ctx['t0'] <= a < ctx['t1']))\n")
    spec = json.loads((checkout / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny-cut", "source": "a test's own",
                            "file": "bench/configs/tiny-cut.json",
                            "reduced": ["n_layers"], "why": "a cut"})
    spec["workloads"][0]["config"] = "tiny-cut"
    spec["per_layer"].append({
        "name": "prefilled", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "backend prefill",
        "moves": "ttft_p50_ms", "workloads": ["tiny.chat"]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(spec))
    cpu_peaks(checkout)
    fake_decodes(monkeypatch)
    res = run(checkout, compile_cache, capsys, "--trace", "1")
    assert res["correct"] is True, res
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["prefilled"] >= 1 and got["prefilled"] == int(got["prefilled"])
    # by the count: 5 sequences, 2.5 MB at 1 GB/s against 4 ms
    assert got["decode_roofline"] == pytest.approx(62.5)
    assert got["decode_mfu"] == pytest.approx(100 * 5000 / (4e-3 * 1e12))


@pytest.mark.parametrize("count", [None, "attn_only"])
def test_a_block_kind_no_count_knows_leaves_its_readers_out(
        checkout, compile_cache, capsys, monkeypatch, count):
    """A configuration with a block kind that its count (``bench/flops.py``,
    or one of its own that raises ``bench.flops.Uncounted``) cannot
    count: the run still prints its line, the two readers that count
    stay out of it, each saying why on stderr."""
    conf = json.loads((checkout / "bench/configs/tiny-llama.json")
                      .read_text())
    conf["program"].update(block_pattern=["rec", "attn"], lru_width=64,
                           family="hybrid")
    if count:
        conf["count"] = count
        (checkout / "bench/counts").mkdir(exist_ok=True)
        (checkout / f"bench/counts/{count}.py").write_text(
            "from bench.flops import Uncounted\n"
            "def decode_flops(cfg, ctx):\n"
            "    for k in cfg['block_pattern']:\n"
            "        if k != 'attn':\n"
            "            raise Uncounted(f'no count for block kind {k!r}')\n"
            "    return len(ctx)\n"
            "decode_bytes = decode_flops\n")
    (checkout / "bench/configs/tiny-llama.json").write_text(json.dumps(conf))
    cpu_peaks(checkout)
    fake_decodes(monkeypatch)
    assert harness.main(ARGS + ["--trace", "1", "--check", "0"],
                        root=checkout, require_tpu=False,
                        cache=compile_cache) == 0
    out, err = capsys.readouterr()
    res = json.loads(out.strip().splitlines()[-1])
    assert "host_ms_per_step" in res["metrics"]
    assert "decode_roofline" not in res["metrics"]
    assert "decode_mfu" not in res["metrics"]
    for name in ("decode_roofline", "decode_mfu"):
        assert f"{name}: " in err and "no count for block kind 'rec'" in err


def test_a_count_that_fails_otherwise_ends_the_run(
        checkout, compile_cache, capsys, monkeypatch):
    """Only ``flops.Uncounted`` leaves the counting readers out: any other
    error in a count (here a key its configuration lacks) ends the run
    with no result line."""
    conf = json.loads((checkout / "bench/configs/tiny-llama.json")
                      .read_text())
    conf["count"] = "broken_count"
    (checkout / "bench/configs/tiny-llama.json").write_text(json.dumps(conf))
    (checkout / "bench/counts").mkdir(exist_ok=True)
    (checkout / "bench/counts/broken_count.py").write_text(
        "def decode_flops(cfg, ctx):\n"
        "    return cfg['no_such_width'] * len(ctx)\n"
        "def decode_bytes(cfg, ctx):\n"
        "    return len(ctx)\n")
    cpu_peaks(checkout)
    fake_decodes(monkeypatch)
    with pytest.raises(KeyError, match="no_such_width"):
        harness.main(ARGS + ["--trace", "1", "--check", "0"], root=checkout,
                     require_tpu=False, cache=compile_cache)
    assert '"correct"' not in capsys.readouterr().out
