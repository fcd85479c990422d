"""Decode step's share of its roofline: the least time the chip could
take for each traced decode call (the larger of its operations over peak
FLOP/s and its bytes over peak bandwidth, ``bench/flops.py``, at the live
sequences' lengths) over the device time the call took."""

from bench import flops
from bench.metrics_common import traced_decodes


def read(name, ctx):
    pairs = traced_decodes(ctx)
    if not pairs:
        return None
    cfg, peaks = ctx["conf"]["program"], ctx["peaks"]
    least = sum(flops.least_seconds(flops.decode_flops(cfg, c),
                                    flops.decode_bytes(cfg, c), peaks)
                for c, _ in pairs)
    return 100.0 * least / sum(t for _, t in pairs)
