"""The dispatch kernel's share of its roofline, in %: the least time of
every call of the traced window (``costs.call_bytes`` over the device's
peak HBM bandwidth from ``peaks.json``) over the kernel's summed device
time.  Memory-bound by definition.  Moves ``decisions_per_s``."""

from costs import call_bytes, is_sched_kernel


def read(ctx):
    ns, n = ctx.trace.op_ns(is_sched_kernel)
    if not n or ctx.peaks is None:
        return None
    least_s = call_bytes(ctx.n_funcs, ctx.n_workers, ctx.events_per_call).sum() \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns / 1e9)
