"""Run one cell of the chip benchmark once and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, system module and per-layer
metric readers are named in ``BENCHMARK.json`` at the checkout's root (see
``harness.py``).  JAX's first device must be a TPU and the cell's chips
must be there; otherwise the run exits nonzero and prints no result.  The
last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (platform, kind, count,
peak memory; with ``--trace 1`` also ``busy_s`` and ``window_s``),
``breakdown`` with ``--trace 1``, and ``check``: each number the
correctness check compared, beside its limit.
"""

import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
