"""Time to first token, 90th percentile over every request due in the
whole window (one still unserved at its end counts its wait so far).
With some 40 requests in a window this is the fifth-worst request, so
it swings with the few that a clump of arrivals makes wait; the median
(``ttft_p50_ms``) is the end-to-end number."""

import math


def read(name, ctx):
    t0, t1 = ctx["t0"], ctx["t1"]
    w = sorted(((r["stamps"][0] if r["stamps"] else t1) - r["due"]) * 1e3
               for r in ctx["recs"] if t0 <= r["due"] < t1)
    if not w:
        return None
    return w[max(0, math.ceil(0.9 * len(w)) - 1)]
