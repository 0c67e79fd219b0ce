"""Share of the roofline of the decode step (``Model.serve_step``,
``jit_serve_step`` in the trace): per step, the larger of its FLOPs over
peak FLOP/s and its bytes (every weight once, the keys and values of
the positions written so far) over peak HBM bytes/s, summed over the
window's steps, over their summed device time."""
from harness.peaks import decode_step_cost

MODULE = "jit_serve_step"


def read(ctx):
    r, pos = ctx.reduced, ctx.extra.get("decode_positions")
    if r is None or not pos or ctx.peaks is None:
        return None
    times = r.module_calls(MODULE, within=r.window)
    if not times:
        return None
    c, b = ctx.cell.config, ctx.extra["batch"]
    # the traced steps are the window's steps, in order
    pos = pos[:len(times)]
    costs = [decode_step_cost(c, b, p) for p in pos]
    t_min = sum(max(f / ctx.peaks.bf16_flops, n / ctx.peaks.hbm_bytes_s)
                for f, n in costs)
    return 100.0 * t_min / sum(times[:len(pos)])
