"""Engine host step: wall time of ``engine.step()`` less the backend
calls inside it, per step in the window (``host_ms_per_step.burst`` is
the same reading in the burst cell)."""

CALLS = ("prefill_wave", "decode", "splice", "extract")


def read(name, ctx):
    t0, t1 = ctx["t0"], ctx["t1"]
    steps = [(a, b) for n, a, b, _ in ctx["spans"]
             if n == "step" and t0 <= a < t1]
    if not steps:
        return None
    calls = sorted((a, b) for n, a, b, _ in ctx["spans"] if n in CALLS)
    inside = 0.0
    lo, hi = steps[0][0], steps[-1][1]
    for a, b in calls:
        if a >= lo and b <= hi:
            inside += b - a
    total = sum(b - a for a, b in steps)
    return (total - inside) * 1e3 / len(steps)
