"""Share of the dispatch kernel's real events served from a column block it
keeps resident in VMEM, in percent: the summed ``resident`` over the summed
``resident + row_loads`` of the traced window's ``sched.fused`` spans (the
HBM form of ``kernels/sched_step.py`` keeps the first column blocks that a
launch's ARRIVALs bring in, and an event on one of them after its first
ARRIVAL loads nothing from HBM).  None where the window's spans carry no
``resident`` (the VMEM form, a device burst, or a program that keeps no
block), and where there is no trace (``program_spans.py``).  Moves
``decisions_per_s``."""

from pathlib import Path

from program_spans import CALL, TRACE_DIR, _read
from trace_reduce import find_xplane


def read(ctx, path=None):
    path = Path(TRACE_DIR if path is None else path)
    try:
        xplane = path if path.is_file() else find_xplane(path)
    except FileNotFoundError:
        return None
    starts, ends, stats = _read(str(xplane), xplane.stat().st_mtime_ns).get(CALL, ((), (), ()))
    lo, hi = ctx.trace.window
    window = [st for s, e, st in zip(starts, ends, stats)
              if s >= lo and e <= hi and "resident" in st]
    resident = sum(int(st["resident"]) for st in window)
    loads = sum(int(st.get("row_loads", 0)) for st in window)
    return 100.0 * resident / (resident + loads) if resident + loads else None
