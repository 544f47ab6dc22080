"""Trainer layer: share of the window in which the trainer waited
for its next batch (``StepMetrics.stall_s`` of the traced steps)."""


def read(ctx):
    if not ctx.steps or ctx.samples_s <= 0:
        return None
    return 100.0 * sum(s.stall_s for s in ctx.steps) / ctx.samples_s
