"""Model FLOP/s utilization of the train step alone: the FLOPs of the
window's steps over their device time (``jit_train_step`` on chip 0,
which every phase holds, in time order, matched to the window's steps)
times the mesh size each ran on, over the chip's peak bf16 FLOP/s, in %.
Device trace."""
MODULE = "jit_train_step"


def read(ctx):
    r, meshes = ctx.reduced, ctx.extra.get("step_meshes")
    f = ctx.extra.get("train_flops_step")
    if r is None or not r.devices or not meshes or not f or ctx.peaks is None:
        return None
    chip0 = r.devices[min(r.devices)]
    runs = sorted((s, d) for s, d in chip0.modules.get(MODULE, [])
                  if r.window[0] <= s < r.window[1])
    if len(runs) != len(meshes):
        return None
    chip_s = sum(d * 1e-9 * n for (_, d), n in zip(runs, meshes))
    return 100.0 * f * len(meshes) / chip_s / ctx.peaks.bf16_flops
