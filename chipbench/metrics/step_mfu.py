"""Whole step: the model FLOPs the window's experiments required (every
local SGD step's forward and backward, the test-set forward after each
round; ``chipbench/flops.py``) over traced window seconds x chips x the
chip's bf16 peak, in percent. Moves ``updates_per_s``."""

from chipbench import flops


def read(ctx):
    if ctx.peaks is None or ctx.red["window_s"] <= 0:
        return None
    work = sum(flops.experiment_flops(ctx.model, ctx.spec, unit.updates)
               for unit in ctx.units)
    return 100.0 * work / (ctx.red["window_s"] * ctx.chips
                           * ctx.peaks["bf16_flops_per_s"])
