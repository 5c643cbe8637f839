"""``program_roofline`` for a family whose work no shape gives whole: as
there, the trace gives the runs of ``params.program`` and their device
seconds, and what ONE run had to do is the whole window's work over the
window's runs; here the work comes from ``params.work_module``
(``benchmark/<name>.py``: ``kernel_work`` and the program counters it lists
in ``COUNTERS``).  No such program in the trace, or a program without those
counters (an older commit): nothing returned, never 0."""

from .. import work, xplane
from .window_mfu_counted import counted_units


def read(metric, ctx):
    if ctx.peaks is None or not ctx.reduced:
        return None
    p = metric["params"]
    runs = xplane.program_runs(ctx.reduced, p["program"], p.get("has_while"))
    found = runs and counted_units(p, ctx, also=(p["runs_counter"],))
    if not found:
        return None
    (n_runs, dev_s), (mod, units) = runs, found
    c0, c1, _, _ = ctx.span("window")
    window_runs = c1[p["runs_counter"]] - c0[p["runs_counter"]]
    if window_runs <= 0:
        return None
    share = n_runs / window_runs
    units = {k: v * share for k, v in units.items() if isinstance(v, (int, float))}
    flops, nbytes = mod.kernel_work(p["work"], ctx.cell.config, units)
    least, bound = work.least_seconds(flops, nbytes, ctx.peaks)
    if least <= 0:
        return None
    ctx.notes[metric["name"]] = {"bound": bound, "least_s": least, "device_s": dev_s,
                                 "runs_in_trace": n_runs, "runs_in_window": window_runs,
                                 "flops": flops, "bytes": nbytes}
    return 100.0 * least / dev_s
