"""Share of the kernel's events that are padding, in %: the no-op events
``sched_many_fused`` adds to fill its chunks over all the events its
launches ran, summed over the ``sched.fused`` spans of the traced window
(their ``padded`` and ``events`` metadata).  None where the program writes
no such span (``program_spans.py``).  Moves ``decisions_per_s``."""

from program_spans import pad_share


def read(ctx):
    return pad_share(ctx)
