"""Whole-window model FLOP/s utilization: the operations of every prompt
and output token the window processed (``bench/flops.py``) over the
window's seconds times the chip's peak (``mfu.burst``)."""

from bench import flops


def read(name, ctx):
    if not ctx["peaks"]:
        return None
    cfg = ctx["conf"]["program"]
    t0, t1 = ctx["t0"], ctx["t1"]
    ops = 0
    for n, a, b, info in ctx["spans"]:
        if not t0 <= a < t1:
            continue
        if n == "prefill_wave":
            ops += info["n"] * flops.prefill_flops(cfg, info["length"])
        elif n == "decode":
            ops += flops.decode_flops(cfg, info["ctx"])
    return 100.0 * ops / ((t1 - t0) * ctx["peaks"]["bf16_flops"])
