"""What several per-layer readers share."""

import sys

from bench import flops, trace


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


def decode_work(name, ctx) -> list[tuple]:
    """``(operations, bytes, device seconds)`` of each traced decode
    call, by the configuration's count (``ctx["count"]``: its
    ``counts/<count>.py``, or ``bench/flops.py``).  Empty when nothing was
    traced; None, with one line on stderr, when the count raises
    ``flops.Uncounted`` (a block kind it has no count for)."""
    pairs = traced_decodes(ctx)
    count, cfg = ctx["count"], ctx["conf"]["program"]
    try:
        return [(count.decode_flops(cfg, c), count.decode_bytes(cfg, c), t)
                for c, t in pairs]
    except flops.Uncounted as e:
        print(f"{name}: {count.__file__} cannot count "
              f"{cfg.get('name')!r}: {e}", file=sys.stderr, flush=True)
        return None
