"""Device idle share: 1 less the union of device-busy intervals over the
traced window (``idle_share.burst`` is the same reading in the burst
cell)."""

from bench import trace


def read(name, ctx):
    b = trace.busy(ctx["trace"])
    if not b or not ctx["trace"]["ops"]:
        return None
    return 100.0 * (1.0 - b["busy_s"] / b["window_s"])
