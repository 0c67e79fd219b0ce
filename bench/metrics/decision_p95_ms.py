"""95th percentile, over every scheduling pass of the window (one
``Instance`` call that kicks the queue), of its wall time: how long a
submitted, freed or grown job waits on the scheduler itself."""
import numpy as np


def read(ctx):
    d = ctx.spans.get("pass").durations
    return float(np.percentile(d, 95) * 1e3) if d else None
