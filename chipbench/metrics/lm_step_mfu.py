"""Whole step of a LoRA language-model cell: the model FLOPs the window's
experiments required (every local SGD step's forward and its backward
through the frozen base, no base-weight gradient; the test windows'
forward after each round; the configuration's reference module counts
them, ``chipbench/flops.py``) over traced window seconds x chips x the
chip's bf16 peak, in percent: ``step_mfu``'s rule. Moves
``updates_per_s``."""

from chipbench import flops


def read(ctx):
    if ctx.peaks is None or ctx.red["window_s"] <= 0:
        return None
    work = sum(flops.experiment_flops(ctx.model, ctx.spec, unit.updates)
               for unit in ctx.units)
    return 100.0 * work / (ctx.red["window_s"] * ctx.chips
                           * ctx.peaks["bf16_flops_per_s"])
