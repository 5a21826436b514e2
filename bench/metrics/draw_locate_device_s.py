"""The USR locate (core/probe.get_rows): root and child searches and the
mixed-radix split.

Device busy seconds per draw in the program scope ``usr_locate``: the union of
the device operations under the benchmark's ``draw`` span whose op-name
path holds the scope, averaged over the window's draws. None where the
program opens no such scope."""
from bench import scopes


def read(ctx):
    return scopes.draw_scope_s(ctx.trace, "usr_locate")
