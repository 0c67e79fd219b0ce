"""Model FLOP/s utilization of the whole training loop: the FLOPs the
window's steps needed (``harness.train.train_step_flops``: forward and
backward, no recomputation), over the chip-seconds the job held (chips
held times wall time, summed over the window's phases, resizes
included), over the chip's peak bf16 FLOP/s, in %.  Host clock."""


def read(ctx):
    f, chip_s = ctx.extra.get("train_flops_step"), ctx.extra.get("chip_seconds")
    steps = ctx.extra.get("step_meshes")
    if not f or not chip_s or not steps or ctx.peaks is None:
        return None
    return 100.0 * f * len(steps) / chip_s / ctx.peaks.bf16_flops
