"""Scheduler queue wait: from a request's due time to the start of the
engine step that prefilled it, 90th percentile over the requests due in
the window that were admitted in it."""

import math


def read(name, ctx):
    t0, t1 = ctx["t0"], ctx["t1"]
    w = sorted((r["first_step"] - r["due"]) * 1e3 for r in ctx["recs"]
               if t0 <= r["due"] < t1 and r["first_step"] is not None
               and r["first_step"] < t1)
    if not w:
        return None
    return w[max(0, math.ceil(0.9 * len(w)) - 1)]
