"""Programs jax compiled (or fetched from the cache) between the window's
first and last instant: its ``backend_compile_duration`` events.  0 is the
expected reading and is reported."""


def read(metric, ctx):
    return float(ctx.meter1["programs"] - ctx.meter0["programs"])
