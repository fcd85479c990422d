"""Model decode step: device-busy time inside each ``decode`` call of the
traced window, from the profiler trace, per call."""

from bench import trace


def read(name, ctx):
    calls = trace.per_call(ctx["trace"], "decode")
    calls = [c for c in calls if c > 0]
    return sum(calls) * 1e3 / len(calls) if calls else None
