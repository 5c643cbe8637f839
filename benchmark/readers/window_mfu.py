"""The whole step's share of the chip's peak: operations the algorithm needs
for what reached the users inside the window (``work.window_flops``), over
the window's seconds and the chips' peak bf16 FLOP/s.  Host-clock window, so
it counts idle time and every inefficiency; it is not a kernel's roofline."""

from .. import work


def read(metric, ctx):
    if ctx.peaks is None:
        return None
    c0, c1, ta, tb = ctx.span("window")
    units = ctx.session.work_units(ta, tb, c0, c1)
    flops = work.window_flops(ctx.cell.config, units)
    if flops <= 0:
        return None
    return 100.0 * flops / (tb - ta) / (ctx.peaks["bf16_flops_per_s"] * ctx.cell.chips)
