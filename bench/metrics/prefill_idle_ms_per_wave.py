"""Backend prefill: device-idle ms inside each ``engine.prefill`` span
lying wholly in the traced part, mean per wave (the host's share of an
admission wave, while the chip waits)."""

from bench import program_spans


def read(name, ctx):
    return program_spans.prefill_idle_ms_per_wave(ctx["trace"])
