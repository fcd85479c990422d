"""Decode step's model FLOP/s utilization: the operations of the live
sequences' tokens (by the configuration's count, ``bench/flops.py``
unless it names one) over the device time of the traced decode calls
times the chip's peak."""

from bench.metrics_common import decode_work


def read(name, ctx):
    work = decode_work(name, ctx)
    if not work:
        return None
    ops = sum(f for f, _, _ in work)
    return 100.0 * ops / (sum(t for _, _, t in work)
                          * ctx["peaks"]["bf16_flops"])
