"""Idle device time of the traced stretch per step (iteration) in it, ms."""


def read(ctx):
    tr, steps = ctx["trace"], ctx["counters"].get("traced_steps")
    if tr is None or not steps or tr.busy_s <= 0:
        return None
    return 1e3 * tr.idle_s() / steps
