"""Programs compiled while the window ran (``jax.monitoring``'s backend
compile events): each one is a stall some request waited through."""


def read(name, ctx):
    return float(ctx["counters"]["compiles_in_window"])
