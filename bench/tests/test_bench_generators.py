"""The open-loop generator: seeded, in seconds, within its ranges, and
offering every seed the same work."""

import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench.generators import open_loop

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


def chat(rate=2.0):
    return {"process": "open_loop", "arrivals": {"rate": rate},
            "round_to": 128,
            "classes": {"chat": {
                "share": 1.0,
                "prompt": {"median": 384, "sigma": 0.8, "lo": 128,
                           "hi": 1536},
                "output": {"median": 80, "sigma": 0.7, "lo": 16,
                           "hi": 512}}}}


def burst():
    return {"process": "open_loop",
            "arrivals": {"rate": 3.0, "on_s": 5, "off_s": 5,
                         "off_rate_share": 0.2},
            "round_to": 128, "sla": True,
            "classes": {
                "interactive": {"share": 45, "gang": 1,
                                "prompt": {"median": 256, "sigma": 0.6,
                                           "lo": 128, "hi": 1024},
                                "output": {"median": 64, "sigma": 0.5,
                                           "lo": 8, "hi": 256}},
                "batch": {"share": 20, "gang": 4,
                          "prompt": {"median": 1024, "sigma": 0.4,
                                     "lo": 256, "hi": 1536},
                          "output": {"median": 384, "sigma": 0.3,
                                     "lo": 64, "hi": 512}}}}


def test_same_seed_same_requests():
    a = open_loop.generate(chat(), 2**33 + 5, 64000, 40.0)
    b = open_loop.generate(chat(), 2**33 + 5, 64000, 40.0)
    assert [r.due for r in a] == [r.due for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [r.max_new for r in a] == [r.max_new for r in b]


def test_due_times_in_seconds_within_horizon():
    reqs = open_loop.generate(chat(rate=2.5), 3, 64000, 40.0)
    dues = [r.due for r in reqs]
    assert dues == sorted(dues)
    assert 0.0 < dues[0] and dues[-1] < 40.0
    # 2.5 requests a second over 40 seconds
    assert len(reqs) == 100


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 11])
def test_lengths_within_ranges_and_rounded(seed):
    reqs = open_loop.generate(chat(), seed, 64000, 60.0)
    for r in reqs:
        assert 128 <= len(r.prompt) <= 1536 and len(r.prompt) % 128 == 0
        assert 16 <= r.max_new <= 512
        assert r.prompt.dtype == np.int32
        assert r.prompt.min() >= 1 and r.prompt.max() < 64000


def test_every_seed_offers_the_same_work():
    a = open_loop.generate(chat(), 1, 64000, 60.0)
    b = open_loop.generate(chat(), 2, 64000, 60.0)
    assert Counter(len(r.prompt) for r in a) == \
        Counter(len(r.prompt) for r in b)
    assert Counter(r.max_new for r in a) == Counter(r.max_new for r in b)
    gaps = lambda rs: sorted(np.round(np.diff([0] + [r.due for r in rs]), 6))
    assert [r.due for r in a] != [r.due for r in b]
    # the gaps are the same multiset up to the first one's offset
    assert abs(sum(gaps(a)) - sum(gaps(b))) < 1.0


@pytest.mark.parametrize("seeds", [(1, 2), (3, 2**31 + 7)])
def test_every_seed_puts_the_same_work_in_the_window(seeds):
    """The ramp is drawn apart from the window, so the requests due in
    the window are the same in number and size for every seed."""
    t = dict(chat(rate=0.8), ramp_s=8)
    a, b = (open_loop.generate(t, s, 64000, 59.0) for s in seeds)
    win = lambda rs: [r for r in rs if r.due >= 8.0]
    assert len(win(a)) == len(win(b)) == round(0.8 * 51)
    assert Counter((len(r.prompt), r.max_new) for r in win(a)) == \
        Counter((len(r.prompt), r.max_new) for r in win(b))
    assert [r.due for r in a] != [r.due for r in b]


def test_stratified_median_matches_the_file():
    lengths = open_loop.stratified_lengths(
        999, {"median": 80, "sigma": 0.7, "lo": 16, "hi": 512})
    assert lengths[499] == 80
    assert lengths == sorted(lengths)


def test_onoff_mean_rate_and_phases():
    t = burst()
    inten = open_loop.Intensity(t["arrivals"])
    assert inten.cumulative(10.0) == pytest.approx(30.0)
    assert inten.off_rate == pytest.approx(0.6)
    assert inten.inverse(inten.cumulative(7.3)) == pytest.approx(7.3)
    reqs = open_loop.generate(t, 9, 64000, 100.0)
    on = sum(1 for r in reqs if (r.due % 10.0) < 5.0)
    assert on > 3 * (len(reqs) - on)


def test_gangs_share_one_prompt_and_due_time():
    reqs = open_loop.generate(burst(), 4, 64000, 60.0)
    gangs = {}
    for r in reqs:
        if r.gang is not None:
            gangs.setdefault(r.gang, []).append(r)
    assert gangs and all(len(g) == 4 for g in gangs.values())
    for g in gangs.values():
        assert len({x.due for x in g}) == 1
        assert all(np.array_equal(x.prompt, g[0].prompt) for x in g)
        assert all(x.sla == "batch" for x in g)


def test_shapes_lists_every_length_the_mix_can_form():
    s = open_loop.shapes(chat())
    assert s["prompt_lengths"] == list(range(128, 1537, 128))
    assert s["gangs"] == [1]


@pytest.mark.parametrize("path", sorted(TRAFFIC.glob("*.json")),
                         ids=lambda p: p.name)
def test_committed_traffic_fits_its_cache(path):
    """Every committed mix: the longest prompt plus the longest output
    fits the cache of each configuration that serves it."""
    t = json.loads(path.read_text())
    reqs = open_loop.generate(t, 0, 64000, 30.0)
    assert reqs
    longest = max(c["prompt"]["hi"] + c["output"]["hi"]
                  for c in t["classes"].values())
    assert longest <= 2048
