"""Mean device time of one prefill (``Model.prefill_step``,
``jit_prefill_step`` in the trace) over the window's batches: the stall
every batch's requests wait before their first token."""
MODULE = "jit_prefill_step"


def read(ctx):
    r = ctx.reduced
    if r is None:
        return None
    times = r.module_calls(MODULE, within=r.window)
    return 1e3 * sum(times) / len(times) if times else None
