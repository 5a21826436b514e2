"""Executables built (compiled, or loaded from the persistent cache) inside
the measured window; a retrace per flush shows here."""


def read(ctx):
    return ctx.counters.get("window_compiles")
