"""Device idle share of the traced window on chip 0, which every phase of
the elastic job holds: 1 - (union of the intervals in which some XLA
operation ran on it) / (the window), in %."""
from harness.trace import clip, union_ns


def read(ctx):
    r = ctx.reduced
    if r is None or not r.devices or r.window_s <= 0:
        return None
    chip0 = r.devices[min(r.devices)]
    busy = union_ns(clip(chip0.ops, r.window)) * 1e-9
    return 100.0 * (1.0 - busy / r.window_s)
