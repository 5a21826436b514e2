"""Percent of the HBM roofline of the full join: the bytes it must move
over peak bandwidth, against its device busy time."""
from bench.metrics import roofline_bytes


def read(ctx):
    t = ctx.trace.span_device_s("full_join")
    n = ctx.counters.get("join_size")
    if not t or n is None:
        return None
    b = roofline_bytes.flatten_bytes(ctx.cell.config["tree"], ctx.tables, n)
    return roofline_bytes.share(b, t, ctx.peaks)
