"""Open-loop traffic from a traffic file: due times in seconds, lengths
in tokens, the same work for every seed.

A traffic file names its arrival process and its request classes:

* ``arrivals``: ``{"rate": r}`` is Poisson at ``r`` requests a second;
  adding ``"on_s"``, ``"off_s"`` and ``"off_rate_share"`` makes it an
  on/off burst whose mean over a period is still ``r`` and whose off
  phases run at ``off_rate_share * r``.
* ``classes``: name -> ``{"share", "prompt", "output", "gang"}``, with
  ``prompt`` and ``output`` clipped lognormals ``{"median", "sigma",
  "lo", "hi"}`` in tokens.  A class with ``gang`` n > 1 arrives as n
  requests at one due time sharing one prompt.
* ``round_to``: prompt lengths are rounded up to a multiple of this.

The arithmetic is that of the program's ``serving/workload.py``
(Poisson and on/off arrivals, clipped lognormal lengths) with due times
in seconds instead of engine steps.  Sizes and gaps are stratified:
every seed draws the same requests (prompt and output lengths, paired
by one fixed shuffle), classes and unit-rate gaps (the quantiles
``(i + 0.5) / n``), and the seed only orders them and picks the token
ids.  Two seeds then offer the same work in a different order, so the
spread between seeds is the system's and not the generator's.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Req:
    due: float                  # seconds after the traffic clock starts
    prompt: np.ndarray          # (S,) int32 token ids
    max_new: int                # output tokens, the prefill's included
    sla: Optional[str] = None
    gang: Optional[str] = None


def lognormal_quantile(p: float, median: float, sigma: float) -> float:
    return median * math.exp(sigma * NormalDist().inv_cdf(p))


def stratified_lengths(n: int, spec: dict, round_to: int = 1) -> list[int]:
    """The ``n`` quantiles ``(i + 0.5) / n`` of a clipped lognormal,
    rounded up to a multiple of ``round_to``, in increasing order."""
    out = []
    for i in range(n):
        x = lognormal_quantile((i + 0.5) / n, spec["median"], spec["sigma"])
        x = min(spec["hi"], max(spec["lo"], x))
        out.append(int(math.ceil(x / round_to) * round_to))
    return out


class Intensity:
    """Piecewise-constant arrival rate: constant, or on/off periods."""

    def __init__(self, arrivals: dict):
        self.rate = float(arrivals["rate"])
        self.on_s = float(arrivals.get("on_s", 0.0))
        self.off_s = float(arrivals.get("off_s", 0.0))
        if self.on_s > 0:
            share = float(arrivals.get("off_rate_share", 0.0))
            period = self.on_s + self.off_s
            self.off_rate = share * self.rate
            # the on rate that keeps the period's mean at ``rate``
            self.on_rate = (self.rate * period
                            - self.off_rate * self.off_s) / self.on_s
        else:
            self.on_rate = self.off_rate = self.rate

    def cumulative(self, t: float) -> float:
        """Expected arrivals in [0, t)."""
        if self.on_s <= 0:
            return self.rate * t
        period = self.on_s + self.off_s
        k, r = divmod(t, period)
        per = self.on_rate * self.on_s + self.off_rate * self.off_s
        part = self.on_rate * min(r, self.on_s) \
            + self.off_rate * max(0.0, r - self.on_s)
        return k * per + part

    def inverse(self, m: float) -> float:
        """The time at which ``cumulative`` reaches ``m``."""
        if self.on_s <= 0:
            return m / self.rate
        period = self.on_s + self.off_s
        per = self.on_rate * self.on_s + self.off_rate * self.off_s
        k, r = divmod(m, per)
        on_part = self.on_rate * self.on_s
        if r <= on_part:
            return k * period + r / self.on_rate
        return k * period + self.on_s + (r - on_part) / self.off_rate


def generate(traffic: dict, seed: int, vocab: int,
             horizon_s: float) -> list[Req]:
    """Every request due in ``[0, horizon_s)``, in due order.

    The traffic file's ``ramp_s`` (the load before the measured window)
    and the rest of the horizon are drawn apart, each with its own
    stratified work, so every seed puts the same requests in the window
    and only their order differs."""
    rng = np.random.default_rng(seed)
    ramp = min(float(traffic.get("ramp_s", 0.0)), horizon_s)
    out: list[Req] = []
    for a, b in ((0.0, ramp), (ramp, horizon_s)):
        if b > a:
            out.extend(_segment(traffic, rng, vocab, a, b, len(out)))
    return out


def _segment(traffic: dict, rng, vocab: int, a: float, b: float,
             first: int) -> list[Req]:
    """The requests due in ``[a, b)``: as many arrivals as the rate
    expects there, with stratified gaps, classes and lengths."""
    inten = Intensity(traffic["arrivals"])
    round_to = int(traffic.get("round_to", 1))
    classes = traffic["classes"]
    names = sorted(classes)
    m0 = inten.cumulative(a)
    mass = inten.cumulative(b) - m0
    # arrivals (a gang is one arrival) expected in the segment
    n = max(1, int(round(mass)))
    # unit-rate exponential gaps, stratified, then ordered by the seed
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    rng.shuffle(gaps)
    # each arrival sits in the middle of its gap, so all lie inside
    marks = (np.cumsum(gaps) - 0.5 * gaps) * (mass / gaps.sum())
    dues = [inten.inverse(m0 + float(m)) for m in marks]
    # classes by their shares, stratified, then ordered by the seed
    shares = np.array([classes[c]["share"] for c in names], float)
    counts = np.floor(shares / shares.sum() * n).astype(int)
    rest = n - counts.sum()
    order = np.argsort(-(shares / shares.sum() * n - counts), kind="stable")
    counts[order[:rest]] += 1
    labels = np.repeat(np.arange(len(names)), counts)
    rng.shuffle(labels)
    sizes = {}
    for ci, c in enumerate(names):
        k = int(counts[ci])
        spec = classes[c]
        p = stratified_lengths(k, spec["prompt"], round_to)
        o = stratified_lengths(k, spec["output"])
        # prompt and output lengths paired by one fixed shuffle (they are
        # drawn independently), so every seed serves the same requests
        pair = np.random.default_rng(k).permutation(k)
        sizes[c] = [(p[i], o[pair[i]]) for i in rng.permutation(k)]
    used = {c: 0 for c in names}
    out: list[Req] = []
    for j, (due, ci) in enumerate(zip(dues, labels), start=first):
        c = names[int(ci)]
        plen, olen = sizes[c][used[c]]
        used[c] += 1
        prompt = rng.integers(1, vocab, plen).astype(np.int32)
        gang = int(classes[c].get("gang", 1))
        sla = c if traffic.get("sla") else None
        for _ in range(gang):
            out.append(Req(due, prompt, olen, sla,
                           f"g{j}" if gang > 1 else None))
    return out


def shapes(traffic: dict) -> dict:
    """What the mix can form: the prompt lengths, and the gang sizes."""
    lengths = set()
    gangs = set()
    for spec in traffic["classes"].values():
        lo, hi = spec["prompt"]["lo"], spec["prompt"]["hi"]
        r = int(traffic.get("round_to", 1))
        first = int(math.ceil(lo / r) * r)
        lengths.update(range(first, int(math.ceil(hi / r) * r) + 1, r))
        gangs.add(int(spec.get("gang", 1)))
    return {"prompt_lengths": sorted(lengths), "gangs": sorted(gangs)}
