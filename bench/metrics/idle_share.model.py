"""Device idle share of the traced window: 1 - (union of the intervals
in which some XLA operation ran on the chip) / (the window), in %."""


def read(ctx):
    share = None if ctx.reduced is None else ctx.reduced.idle_share()
    return None if share is None else 100.0 * share
