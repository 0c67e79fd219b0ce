"""Mean host time of one batched feasibility scan
(``FlatGraph.feasible_roots_batch``), from the benchmark's wrapper:
the pad, the copies to and from the device and the kernel."""


def read(ctx):
    return ctx.spans.get("scan_call").mean_ms()
