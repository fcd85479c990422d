"""Decode step's share of its roofline: the least time the chip could
take for each traced decode call (the larger of its operations over peak
FLOP/s and its bytes over peak bandwidth, by the configuration's count,
``bench/flops.py`` unless it names one, at the live sequences' lengths)
over the device time the call took."""

from bench import flops
from bench.metrics_common import decode_work


def read(name, ctx):
    work = decode_work(name, ctx)
    if not work:
        return None
    least = sum(flops.least_seconds(f, b, ctx["peaks"]) for f, b, _ in work)
    return 100.0 * least / sum(t for _, _, t in work)
