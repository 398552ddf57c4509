"""Host build (``api.build_experiment``: data, partition, fleet,
``device_put``): mean milliseconds per experiment built in the window, from
the benchmark's ``build`` spans. Moves ``updates_per_s``."""


def read(ctx):
    d = [t1 - t0 for name, t0, t1 in ctx.spans if name == "build"]
    return 1e3 * sum(d) / len(d) if d else None
