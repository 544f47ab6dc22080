"""Kernels layer: the ``dense_unpack`` decode kernel's share of its roofline.
Logical bytes are the presence bitmaps and present values read and the
float32 column written for every dense feature the job reads
(``chipbench.cost.dense_unpack_bytes``), one launch per stripe; the time is
the summed device time of the kernel's events in the trace.  Bound by
HBM bandwidth: it does no arithmetic worth counting."""

KERNEL = "dense_unpack"


def read(ctx):
    if ctx.profile is None or ctx.peaks is None or ctx.wm0 is None or ctx.wm1 is None:
        return None
    events = ctx.profile.kernel(KERNEL)
    seconds = sum(e.dur for e in events)
    if not events or seconds <= 0:
        return None
    per_stripe = ctx.cost.dense_unpack_bytes(ctx.pool.raw, ctx.pool.job,
                                            ctx.config["stripe_rows"])
    launches_per_stripe = 1.0
    if launches_per_stripe <= 0:
        return None
    logical = len(events) / launches_per_stripe * per_stripe
    return 100.0 * logical / ctx.peaks["hbm_bytes_per_s"] / seconds
