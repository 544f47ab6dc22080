"""DPP transform layer: the workers' transform seconds per thousand rows
decoded, over the window (``WorkerMetrics.transform_s`` and
``rows_decoded`` deltas)."""


def read(ctx):
    if ctx.wm0 is None or ctx.wm1 is None:
        return None
    rows = ctx.wm1.rows_decoded - ctx.wm0.rows_decoded
    if rows <= 0:
        return None
    return 1e6 * (ctx.wm1.transform_s - ctx.wm0.transform_s) / rows
