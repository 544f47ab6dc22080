"""DPP load layer: the workers' load seconds per thousand rows
decoded, over the window (``WorkerMetrics.load_s`` and
``rows_decoded`` deltas)."""


def read(ctx):
    if ctx.wm0 is None or ctx.wm1 is None:
        return None
    rows = ctx.wm1.rows_decoded - ctx.wm0.rows_decoded
    if rows <= 0:
        return None
    return 1e6 * (ctx.wm1.load_s - ctx.wm0.load_s) / rows
