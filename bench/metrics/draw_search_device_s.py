"""The searches (kernels/ops), under whichever phase searched.

Device busy seconds per draw in the program scope ``search``: the union of
the device operations under the benchmark's ``draw`` span whose op-name
path holds the scope, averaged over the window's draws. None where the
program opens no such scope."""
from bench import scopes


def read(ctx):
    return scopes.draw_scope_s(ctx.trace, "search")
