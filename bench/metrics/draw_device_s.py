"""Device busy seconds per draw: the union of device operations under the
benchmark's ``draw`` span, averaged over the window's draws."""


def read(ctx):
    return ctx.trace.span_device_s("draw")
