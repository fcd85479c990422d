"""Scheduler: host ms in the program's ``engine.schedule`` spans (wakes,
rebalance, preemption, the claim loop over the slots) per engine step of
the window, from the span log of the traced run."""

from bench import program_spans


def read(name, ctx):
    return program_spans.sched_ms_per_step(ctx["program_spans"], ctx["t0"],
                                           ctx["t1"])
