"""Device step: model FLOPs of the samples trained in the window,
over the window and the chips' bf16 peak.  The FLOPs per sample come
from the configuration (``chipbench.cost.model_flops_per_sample``)."""


def read(ctx):
    if ctx.peaks is None or ctx.samples <= 0 or ctx.samples_s <= 0:
        return None
    flops = ctx.cost.model_flops_per_sample(ctx.config["model"]) * ctx.samples
    return 100.0 * flops / ctx.samples_s / (ctx.cell.chips * ctx.peaks["bf16_flops"])
