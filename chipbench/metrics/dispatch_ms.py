"""Driver and scanned program (``FLExperiment.run`` to the history on the
host): mean milliseconds per run call in the window, from the benchmark's
``dispatch`` spans. Moves ``updates_per_s``."""


def read(ctx):
    d = [t1 - t0 for name, t0, t1 in ctx.spans if name == "dispatch"]
    return 1e3 * sum(d) / len(d) if d else None
