"""Device busy seconds per full join: the union of device operations under
the benchmark's ``full_join`` span, averaged over the window's joins."""


def read(ctx):
    return ctx.trace.span_device_s("full_join")
