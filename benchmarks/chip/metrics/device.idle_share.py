"""Share of the traced window, in %, in which no operation ran on the
device: 1 - (union of device op intervals / window).  Moves
``decision_ms_p95``."""


def read(ctx):
    if not ctx.trace.window_ns or not len(ctx.trace.op_names):
        return None
    return 100.0 * (1.0 - ctx.trace.busy_ns / ctx.trace.window_ns)
