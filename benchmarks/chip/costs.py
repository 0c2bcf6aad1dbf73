"""What the pull-dispatch kernel must move, counted per dispatcher call.

The least work one ``sched_many_fused`` call can do is to read and write
the idle table ``(F, W)`` and the connection row ``(W,)`` once, all int32,
and to read each real event (three int32) and write its answer (worker
int32, warm flag int32): ``2 * 4 * (F + 1) * W + 20 * events`` bytes.  It
is counted per call, not per chunk or per kernel launch, so the count
reads the same work whatever implements it.  The call does no arithmetic
that a peak rate bounds, so its least time is the bytes over the peak HBM
bandwidth: the roofline is memory-bound by this definition.
"""

TABLE_BYTES_PER_CELL = 2 * 4   # each int32 cell read once and written once
EVENT_BYTES = 3 * 4 + 2 * 4    # (kind, func, worker) in, (worker, warm) out


def call_bytes(n_funcs: int, n_workers: int, n_events):
    """Least bytes one call moves; ``n_events`` may be an array of calls."""
    return TABLE_BYTES_PER_CELL * (n_funcs + 1) * n_workers + EVENT_BYTES * n_events


def is_sched_kernel(op: str) -> bool:
    """Whether a device op of the trace (``trace_reduce.op_label``) is the
    dispatch kernel: the Pallas custom call that XLA names after
    ``kernels/ops._sched_events``."""
    return op.startswith("_sched_events") and op.endswith(" custom-call")
