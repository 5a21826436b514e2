"""The gather of float64 columns as their bit patterns
(core/probe.gather_columns, nested in ``usr_gather``).

Device busy seconds per ``draw`` span (one call: a single draw, or a batch
of draws) in the program scope ``usr_gather_bits``. None where no device
operation carries the scope: a program without it, or a cell whose
columns hold no float64."""
from bench import scopes


def read(ctx):
    return scopes.draw_scope_s(ctx.trace, "usr_gather_bits") or None
