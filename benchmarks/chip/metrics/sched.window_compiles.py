"""Backend compiles inside the traced window: the ``compiles`` metadata of
the window's ``sched.fused`` spans, summed (each the program's own count of
JAX's compile events across one ``sched_many_fused`` call; a load from the
persistent cache counts too).  None where the program writes no such span
(``program_spans.py``).  Moves ``decision_ms_p95``."""

from program_spans import window_compiles


def read(ctx):
    return window_compiles(ctx)
