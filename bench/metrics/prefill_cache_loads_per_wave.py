"""Entry points and JIT: programs compiled or loaded from the persistent
cache inside the window's ``engine.prefill`` spans, per admission wave,
from the counts the span log keeps on its innermost open span.  0 once
every prefill shape is in memory."""

from bench import program_spans


def read(name, ctx):
    return program_spans.prefill_cache_loads_per_wave(
        ctx["program_spans"], ctx["t0"], ctx["t1"])
