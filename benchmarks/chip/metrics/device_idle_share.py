"""Device layer: share of the window in which no operation ran on
the chip (the union of the device planes' op intervals, averaged over
the chips)."""


def read(ctx):
    p = ctx.profile
    if p is None or p.window_s <= 0:
        return None
    return 100.0 * (1.0 - p.busy_s / p.window_s)
