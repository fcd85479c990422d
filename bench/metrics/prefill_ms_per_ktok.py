"""Backend prefill: host time inside ``prefill_wave`` (which ends in a
host read of the first tokens) per 1000 prompt tokens, in the window."""


def read(name, ctx):
    t0, t1 = ctx["t0"], ctx["t1"]
    sec = tok = 0.0
    for n, a, b, info in ctx["spans"]:
        if n == "prefill_wave" and t0 <= a < t1:
            sec += b - a
            tok += info["n"] * info["length"]
    return sec * 1e3 / (tok / 1e3) if tok else None
