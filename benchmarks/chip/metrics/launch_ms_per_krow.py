"""DPP kernels, host side: the host seconds of every DPP Pallas launch,
decode and transform, from the first copy in to the result back on the
host (``WorkerMetrics.launch_s``), per thousand rows decoded, over the
window.  None where the program has no such counter."""

FIELD = "launch_s"


def read(ctx):
    if ctx.wm0 is None or ctx.wm1 is None:
        return None
    s0, s1 = getattr(ctx.wm0, FIELD, None), getattr(ctx.wm1, FIELD, None)
    rows = ctx.wm1.rows_decoded - ctx.wm0.rows_decoded
    if s0 is None or s1 is None or rows <= 0:
        return None
    return 1e6 * (s1 - s0) / rows
