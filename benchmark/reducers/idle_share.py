"""Device idle share of the traced stretch: 1 - union of the device-op
intervals over the stretch, in per cent."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window_s <= 0 or tr.busy_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
