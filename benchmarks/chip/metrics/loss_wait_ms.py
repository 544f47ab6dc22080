"""Trainer layer: mean of the ``train.wait`` span, in which the host waits
on ``float(loss)`` for the device step it dispatched, over the window."""


def read(ctx):
    d = [s.t1 - s.t0 for s in ctx.spans if s.name == "train.wait"]
    return 1e3 * sum(d) / len(d) if d else None
