"""DPP worker threads: the CPU seconds of the workers' producer and
consumer threads over the seconds they spent in extract, transform and
load, over the window (``WorkerMetrics.cpu_s`` against ``extract_s +
transform_s + load_s`` deltas).  Below 100% a thread waited: on the
fetch, on the device, or for the interpreter lock.  None where the
program has no such counter."""


def read(ctx):
    if ctx.wm0 is None or ctx.wm1 is None:
        return None
    c0, c1 = getattr(ctx.wm0, "cpu_s", None), getattr(ctx.wm1, "cpu_s", None)
    busy = sum(getattr(ctx.wm1, k) - getattr(ctx.wm0, k)
               for k in ("extract_s", "transform_s", "load_s"))
    if c0 is None or c1 is None or busy <= 0:
        return None
    return 100.0 * (c1 - c0) / busy
