"""DPP load layer: the share of the padded batches' id slots that no id
fills, over the window: 100 x (1 - ids / slots), from the workers'
``WorkerMetrics.load_ids`` and ``load_id_slots`` deltas.  None where the
program has no such counters, or made no batch in the window."""


def read(ctx):
    if ctx.wm0 is None or ctx.wm1 is None:
        return None
    got = [getattr(w, k, None) for w in (ctx.wm0, ctx.wm1)
           for k in ("load_id_slots", "load_ids")]
    if None in got or got[2] <= got[0]:
        return None
    slots0, ids0, slots1, ids1 = got
    return 100.0 * (1.0 - (ids1 - ids0) / (slots1 - slots0))
