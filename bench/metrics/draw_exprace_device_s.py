"""EXPRACE, the draw's positions (core/sampling.exprace_positions).

Device busy seconds per draw in the program scope ``exprace``: the union of
the device operations under the benchmark's ``draw`` span whose op-name
path holds the scope, averaged over the window's draws. None where the
program opens no such scope."""
from bench import scopes


def read(ctx):
    return scopes.draw_scope_s(ctx.trace, "exprace")
