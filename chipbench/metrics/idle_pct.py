"""Device (v5e): the share of the traced window in which no operation ran
on a chip, averaged over the cell's chips: 1 - union(op intervals) /
window, in percent. Moves ``updates_per_s``."""


def read(ctx):
    busy = ctx.red["busy_s"]
    if not busy or ctx.red["window_s"] <= 0:
        return None
    mean = sum(busy.values()) / len(busy)
    return 100.0 * (1.0 - mean / ctx.red["window_s"])
