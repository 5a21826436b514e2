"""Percent of the HBM roofline of the draw: the bytes it must move over
peak bandwidth, against its device busy time."""
from bench.metrics import roofline_bytes


def read(ctx):
    t = ctx.trace.span_device_s("draw")
    k = ctx.counters.get("mean_count")
    if not t or k is None:
        return None
    cfg = ctx.cell.config
    b = roofline_bytes.draw_bytes(cfg["tree"], ctx.tables, cfg["prob_var"], k)
    return roofline_bytes.share(b, t, ctx.peaks)
