"""Engine host step: device-idle ms inside each ``engine.step`` of the
traced part that decodes and neither prefills nor splices, per such step
(the host's time between two decodes, while the chip waits)."""

from bench import program_spans


def read(name, ctx):
    return program_spans.decode_idle_ms_per_step(ctx["trace"])
