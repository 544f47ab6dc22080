"""Kernels layer: the ``fused_transform`` wave kernel's share of its
roofline.  Logical bytes are four in and four out for every value of a
fusable op (``chipbench.cost.fused_transform_bytes``); launches per stripe
come from the workers' counters over the window; the time is the
summed device time of the kernel's events in the trace.  Bound by HBM
bandwidth."""

KERNEL = "fused_transform"


def read(ctx):
    if ctx.profile is None or ctx.peaks is None or ctx.wm0 is None or ctx.wm1 is None:
        return None
    events = ctx.profile.kernel(KERNEL)
    seconds = sum(e.dur for e in events)
    if not events or seconds <= 0:
        return None
    per_stripe = ctx.cost.fused_transform_bytes(ctx.pool.raw, ctx.pool.job,
                                               ctx.config["stripe_rows"])
    stripes = ctx.wm1.stripes_read - ctx.wm0.stripes_read
    if stripes <= 0:
        return None
    launches_per_stripe = (ctx.wm1.fused_launches - ctx.wm0.fused_launches) / stripes
    if launches_per_stripe <= 0:
        return None
    logical = len(events) / launches_per_stripe * per_stripe
    return 100.0 * logical / ctx.peaks["hbm_bytes_per_s"] / seconds
