"""Host time of one dispatcher call, in us: for each ``dispatch.call`` span
(``sched_many_fused`` plus fetching its answers), the span's length less
the device-busy time inside it; the mean over the calls of the traced
window.  Moves ``decision_ms_p95``."""


def read(ctx):
    starts, ends = ctx.trace.spans["dispatch.call"]
    if not len(starts):
        return None
    busy = ctx.trace.busy_between(starts, ends)
    return float(((ends - starts) - busy).mean() / 1e3)
