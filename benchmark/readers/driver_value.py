"""A quantity the traffic driver measures at the clients over the whole
window (``Session.end_to_end``) and the cell does not hold to a bound:
``params.key`` names it.  Read in the traced run, so the profiler's cost is
in it."""


def read(metric, ctx):
    return ctx.e2e.get(metric["params"]["key"])
