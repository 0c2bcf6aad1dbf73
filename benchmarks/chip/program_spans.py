"""The program's own spans inside ``sched_many_fused``, read from the trace.

``repro.core.jax_sched.sched_many_fused`` writes, with
``jax.profiler.TraceAnnotation``, a ``sched.fused`` span around each call,
whose metadata gives ``chunks``, ``events`` (real events handed in),
``padded`` (no-op events added to fill the last chunk) and ``compiles``
(backend compiles during the call), and per chunk a ``sched.stage_in``, a
``sched.launch`` and a ``sched.stage_out`` span (one more
``sched.stage_out`` closes the call).  They are host events of the same
``.xplane.pb`` as the device operations, on the same clock, so the device's
idle time inside each is ``TraceSummary.busy_between`` away.

A reader gets only its ``ctx``, so this module reads the trace where
``harness.main`` writes it, :data:`TRACE_DIR`, once per process and file;
tests pass ``path``.  Only the calls inside the traced window
(``ctx.trace.window``) count.  Where the trace has no ``sched.fused`` span
there, as with a program that writes none, or where there is no trace,
every reader reads ``None``.
"""

from __future__ import annotations

import dataclasses
import functools
from pathlib import Path

import numpy as np

from trace_reduce import find_xplane

TRACE_DIR = Path(__file__).resolve().parents[2] / ".chipbench" / "trace"
CALL = "sched.fused"
STAGES = ("sched.stage_in", "sched.launch", "sched.stage_out")
STATS = ("chunks", "events", "padded", "compiles")


@dataclasses.dataclass
class Calls:
    """The ``sched.fused`` spans of the traced window (ns), their metadata,
    and the stage spans that lie inside them."""

    starts: np.ndarray
    ends: np.ndarray
    stats: dict          # stat name -> per-call int array
    stages: dict         # stage name -> (starts, ends)

    def __len__(self) -> int:
        return len(self.starts)


@functools.lru_cache(maxsize=4)
def _read(path: str, mtime_ns: int):
    """Every ``sched.*`` host event of the trace: name -> (starts, ends,
    stats of each)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    found = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == CALL or ev.name in STAGES:
                    s, e, st = found.setdefault(ev.name, ([], [], []))
                    s.append(ev.start_ns)
                    e.append(ev.start_ns + ev.duration_ns)
                    st.append(dict(ev.stats) if ev.name == CALL else None)
    return found


def calls(ctx, path=None) -> Calls | None:
    """The program's calls inside ``ctx.trace``'s window, or ``None``."""
    path = Path(TRACE_DIR if path is None else path)
    try:
        xplane = path if path.is_file() else find_xplane(path)
    except FileNotFoundError:
        return None
    found = _read(str(xplane), xplane.stat().st_mtime_ns)
    if CALL not in found:
        return None
    s, e, st = found[CALL]
    s, e = np.array(s, float), np.array(e, float)
    lo, hi = ctx.trace.window
    keep = np.flatnonzero((s >= lo) & (e <= hi))
    if not len(keep):
        return None
    keep = keep[np.argsort(s[keep], kind="stable")]
    s, e = s[keep], e[keep]
    stats = {k: np.array([int(st[i].get(k, 0)) for i in keep], np.int64) for k in STATS}
    stages = {}
    for name in STAGES:
        ss, se, _ = found.get(name, ([], [], []))
        ss, se = np.array(ss, float), np.array(se, float)
        i = np.searchsorted(s, ss, side="right") - 1   # the last call opened before
        inside = (i >= 0) & (se <= e[np.maximum(i, 0)])
        stages[name] = (ss[inside], se[inside])
    return Calls(s, e, stats, stages)


def stage_idle_us_per_call(ctx, stage: str, path=None) -> float | None:
    """Device-idle us inside the ``stage`` spans of a call, the mean over
    the calls of the window: each span's length less the device-busy time
    inside it, summed over the window's calls, over their number."""
    c = calls(ctx, path)
    if c is None:
        return None
    s, e = c.stages[stage]
    idle = (e - s) - ctx.trace.busy_between(s, e)
    return float(idle.sum() / len(c) / 1e3)


def pad_share(ctx, path=None) -> float | None:
    """Padding no-ops as a share, in %, of the events the kernel ran."""
    c = calls(ctx, path)
    if c is None:
        return None
    padded = int(c.stats["padded"].sum())
    run = padded + int(c.stats["events"].sum())
    return 100.0 * padded / run if run else None


def window_compiles(ctx, path=None) -> int | None:
    """Backend compiles made inside the window's calls."""
    c = calls(ctx, path)
    return None if c is None else int(c.stats["compiles"].sum())
