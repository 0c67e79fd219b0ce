"""Model FLOP/s utilization of the whole serving loop: the forward FLOPs
the window's prefills and decode steps needed (from the shapes), over
the window's wall time, over the chip's peak bf16 FLOP/s, in %."""


def read(ctx):
    f = ctx.extra.get("model_flops")
    if not f or ctx.peaks is None or not ctx.window_s:
        return None
    return 100.0 * f / ctx.window_s / ctx.peaks.bf16_flops
