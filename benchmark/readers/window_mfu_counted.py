"""``window_mfu`` for a family whose work no shape gives whole: the work
module named by ``params.work_module`` (``benchmark/<name>.py``) counts the
operations from the driver's units AND the window's difference of the program
counters the module lists (``COUNTERS``).  A program without those counters
(an older commit): nothing returned."""

import importlib


def counted_units(params, ctx, also=()):
    """(work module, the driver's units of the window with the module's
    counters added), or None where the program lacks a counter."""
    mod = importlib.import_module("benchmark." + params["work_module"])
    c0, c1, ta, tb = ctx.span("window")
    if any(k not in c1 for k in mod.COUNTERS + tuple(also)):
        return None
    units = ctx.session.work_units(ta, tb, c0, c1)
    units.update({k: c1[k] - c0[k] for k in mod.COUNTERS})
    return mod, units


def read(metric, ctx):
    found = ctx.peaks and counted_units(metric["params"], ctx)
    if not found:
        return None
    mod, units = found
    flops = mod.window_flops(ctx.cell.config, units)
    if flops <= 0:
        return None
    _, _, ta, tb = ctx.span("window")
    return 100.0 * flops / (tb - ta) / (ctx.peaks["bf16_flops_per_s"] * ctx.cell.chips)
