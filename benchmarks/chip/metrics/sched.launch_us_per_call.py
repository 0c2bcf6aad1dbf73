"""Device-idle time per call in the wrapper's kernel launch, in us: for
each ``sched.fused`` span of the traced window (one ``sched_many_fused``
call), the summed length of its ``sched.launch`` spans
(``ops.sched_events``: the backend and VMEM checks and the dispatch of the
jitted kernel) less the device-busy time inside them; the mean over the
calls.  None where the program writes no such span (``program_spans.py``).
Moves ``decision_ms_p95``."""

from program_spans import stage_idle_us_per_call


def read(ctx):
    return stage_idle_us_per_call(ctx, "sched.launch")
