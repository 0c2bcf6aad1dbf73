"""Device ns of the dispatch kernel per function row it brought from HBM:
the kernel's summed op time in the trace over the ``row_loads`` of the
traced window's ``sched.fused`` spans (the rows the HBM form of
``kernels/sched_step.py`` loads, one for each real event while no row stays
in VMEM across events).  None where the window's spans carry no
``row_loads``, or count none (a table held in VMEM loads no rows), and
where there is no trace (``program_spans.py``).  Moves
``decisions_per_s``."""

from pathlib import Path

from costs import is_sched_kernel
from program_spans import CALL, TRACE_DIR, _read
from trace_reduce import find_xplane


def read(ctx, path=None):
    ns, n = ctx.trace.op_ns(is_sched_kernel)
    if not n:
        return None
    path = Path(TRACE_DIR if path is None else path)
    try:
        xplane = path if path.is_file() else find_xplane(path)
    except FileNotFoundError:
        return None
    starts, ends, stats = _read(str(xplane), xplane.stat().st_mtime_ns).get(CALL, ((), (), ()))
    lo, hi = ctx.trace.window
    loads = sum(int(st.get("row_loads", 0))
                for s, e, st in zip(starts, ends, stats) if s >= lo and e <= hi)
    return ns / loads if loads else None
