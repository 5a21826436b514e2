"""Percent of the traced window in which no operation ran on the device;
reads ``device_idle_share.<traffic>`` of every cell."""


def read(ctx):
    return ctx.trace.idle_share()
