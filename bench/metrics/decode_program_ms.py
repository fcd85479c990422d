"""Model decode step, found by the program's name: device time of each
execution of the jitted ``paged_decode`` (``jit_paged_decode`` on the
TPU's module line) lying wholly in the traced part, mean per call.  It
reads the run's profile itself, and leans on no benchmark span around
the backend call as ``decode_step_ms`` does.  A program whose decode
bears no such name reads nothing, and says so on stderr when the
traced part holds the benchmark's ``decode`` calls."""

import sys
from pathlib import Path

from bench import program_spans, trace


def read(name, ctx):
    pf = program_spans.run_profile(ctx, Path(__file__).resolve().parents[1])
    v = program_spans.program_ms(ctx["trace"], pf) if pf else None
    if v is None and any(c > 0 for c in trace.per_call(ctx["trace"],
                                                        "decode")):
        print(f"{name}: the traced part holds decode calls but no "
              f"{program_spans.DECODE_PROGRAM} program"
              + ("" if pf else " (no profile of this window found)"),
              file=sys.stderr, flush=True)
    return v
