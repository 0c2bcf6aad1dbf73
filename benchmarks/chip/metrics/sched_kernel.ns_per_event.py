"""Device ns of the dispatch kernel per real (unpadded) event of the window:
the kernel's summed op time in the trace over the events the calls of the
traced window handed over.  Moves ``decisions_per_s``."""

from costs import is_sched_kernel


def read(ctx):
    ns, n = ctx.trace.op_ns(is_sched_kernel)
    if not n or not ctx.real_events:
        return None
    return ns / ctx.real_events
