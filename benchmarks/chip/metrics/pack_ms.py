"""Trainer layer: mean of the ``train.pack`` span, the host packing of a
padded DPP batch into the fixed multi-hot layout (a child of
``train.put``), over the window.  None where the program packs nothing."""


def read(ctx):
    d = [s.t1 - s.t0 for s in ctx.spans if s.name == "train.pack"]
    return 1e3 * sum(d) / len(d) if d else None
