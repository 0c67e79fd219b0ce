"""Mean host time of one aggregate sweep (``flatgraph.aggregate_sweep``,
jitted on the chip), from the benchmark's wrapper.  The run prints the
device time of ``jit_sweep`` from the trace beside it."""


def read(ctx):
    return ctx.spans.get("sweep_call").mean_ms()
