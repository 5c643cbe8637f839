"""A kernel family's share of its roofline: the least time the chip could
take for the family's runs in the trace (``work.kernel_work`` through
``work.least_seconds``) over the device time of those runs.

``params``: ``program`` (the compiled program's name without its
fingerprint), ``has_while`` (keep only programs that hold, or do not hold, a
scan), ``work`` (the kernel's work function), ``runs_counter`` (the program
counter that counts the family's runs).  The trace gives the runs and their
device seconds; what ONE run had to do is the whole window's work over the
window's runs, from the program's counters and the clients' records (on a
device whose queue is deep the trace's events do not line up with host
times, so counters cannot bracket the traced seconds).  No such program in
the trace: nothing returned, never 0."""

from .. import work, xplane


def read(metric, ctx):
    if ctx.peaks is None:
        return None
    if not ctx.reduced:
        return None
    p = metric["params"]
    runs = xplane.program_runs(ctx.reduced, p["program"], p.get("has_while"))
    if not runs:
        return None
    n_runs, dev_s = runs
    c0, c1, ta, tb = ctx.span("window")
    window_runs = c1[p["runs_counter"]] - c0[p["runs_counter"]]
    if window_runs <= 0:
        return None
    units = ctx.session.work_units(ta, tb, c0, c1)
    share = n_runs / window_runs
    units = {k: v * share for k, v in units.items() if isinstance(v, (int, float))}
    flops, nbytes = work.kernel_work(p["work"], ctx.cell.config, units)
    least, bound = work.least_seconds(flops, nbytes, ctx.peaks)
    if least <= 0:
        return None
    ctx.notes[metric["name"]] = {"bound": bound, "least_s": least, "device_s": dev_s,
                                 "runs_in_trace": n_runs, "runs_in_window": window_runs,
                                 "flops": flops, "bytes": nbytes}
    return 100.0 * least / dev_s
