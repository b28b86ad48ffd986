"""Work over time: ``counters[work] / counters[over]``, all the work of the
window over all of its time."""


def read(ctx, work, over):
    c = ctx["counters"]
    if not c.get(over) or not c.get(work):
        return None
    return c[work] / c[over]
