"""What several per-layer readers share."""

from bench import trace


def traced_decodes(ctx) -> list[tuple]:
    """``(live context lengths, device seconds)`` of each decode call in
    the traced window: the host spans (which know the live sequences) and
    the trace's spans of the same calls, paired in order."""
    if not ctx["peaks"]:
        return []
    dev = trace.per_call(ctx["trace"], "decode")
    t0, t1 = ctx["trace_t0"], ctx["t1"]
    host = [info["ctx"] for n, a, b, info in ctx["spans"]
            if n == "decode" and t0 <= a and b <= t1]
    return [(c, t) for c, t in zip(host, dev) if t > 0 and c]
