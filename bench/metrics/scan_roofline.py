"""Share of the roofline of the feasibility kernel
(``kernels/feasibility._feasible_pallas``, ``jit__feasible_pallas`` in
the trace): bytes of its padded inputs and output, from their shapes,
over peak HBM bytes/s, over the kernel's device time; summed over the
window's calls."""
from harness.peaks import roofline_share

MODULE = "jit__feasible_pallas"


def read(ctx):
    r, nbytes = ctx.reduced, ctx.extra.get("scan_kernel_bytes")
    if r is None or not nbytes or ctx.peaks is None:
        return None
    times = r.module_calls(MODULE, within=r.window)
    if not times:
        return None
    share, _ = roofline_share(0.0, float(sum(nbytes)), sum(times), ctx.peaks)
    return share
