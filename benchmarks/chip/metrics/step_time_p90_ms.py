"""Trainer layer: the 90th percentile of the intervals between
consecutive step completions, over every step of the window.  In a
synchronous data-parallel job each step waits for the slowest trainer's
batch, so this tail sets the cluster's pace."""
import numpy as np


def read(ctx):
    if len(ctx.step_ends) < 2:
        return None
    return 1e3 * float(np.percentile(np.diff(np.asarray(ctx.step_ends)), 90))
