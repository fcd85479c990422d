"""Decode step's model FLOP/s utilization: the operations of the live
sequences' tokens (``bench/flops.py``) over the device time of the traced
decode calls times the chip's peak."""

from bench import flops
from bench.metrics_common import traced_decodes


def read(name, ctx):
    pairs = traced_decodes(ctx)
    if not pairs:
        return None
    cfg, peaks = ctx["conf"]["program"], ctx["peaks"]
    ops = sum(flops.decode_flops(cfg, c) for c, _ in pairs)
    return 100.0 * ops / (sum(t for _, t in pairs) * peaks["bf16_flops"])
