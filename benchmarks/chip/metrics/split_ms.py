"""DPP worker layer: mean of the ``worker.split`` span, from a worker's
acquisition of a split to the delivery of its last batch, over the
splits that began and ended inside the window."""


def read(ctx):
    d = [s.t1 - s.t0 for s in ctx.spans if s.name == "worker.split"]
    return 1e3 * sum(d) / len(d) if d else None
