"""``num / den`` of the program's own counters, as their difference over the
window.  ``den_fact`` multiplies the denominator by a fact of the cell (such
as ``max_batch``); ``scale`` the result.  Nothing counted: nothing returned."""


def read(metric, ctx):
    p = metric["params"]
    c0, c1, _, _ = ctx.span("window")
    if p["num"] not in c1 or p["den"] not in c1:
        return None
    num = c1[p["num"]] - c0[p["num"]]
    den = (c1[p["den"]] - c0[p["den"]]) * ctx.facts.get(p.get("den_fact"), 1)
    if den <= 0:
        return None
    return p.get("scale", 1) * num / den
