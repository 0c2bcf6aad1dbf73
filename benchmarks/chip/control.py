"""The correctness check's control and planted faults, for the dispatch cells.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 7 8 9 --seconds 10

runs the cell through the whole harness with the system under test
replaced by :class:`ControlSUT` -- the plain reference with one guarantee of
the configuration broken: a pull takes the lowest-id worker that holds an
idle instance, not the least-loaded one -- and prints each run's result
line.  Every run must read ``correct: false``.  The benchmark's own runs
never run this.

:data:`FAULTS` are the faults a dispatch cell can have, planted around the
real system under test; ``tests/test_chip_bench.py`` sees each of them
read ``correct: false`` on the CPU.  A one-chip cell has no exchange
between chips to leave out.
"""

from __future__ import annotations

import json
import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from reference import ARRIVAL, PullDispatch  # noqa: E402


class _RefState:
    """A state the control hands back: its reference object, read out as
    dense ``(idle, conns)`` only when asked."""

    def __init__(self, ref):
        self.ref = ref

    def __getitem__(self, i):
        return self.ref.state()[i]


class ControlSUT:
    """The reference with the pull taking the lowest-id holder, in the
    place of ``sched_many_fused(state, events)``."""

    def __call__(self, state, events):
        if isinstance(state, _RefState):
            ref = state.ref
        else:
            ref = PullDispatch(np.asarray(state[0]), np.asarray(state[1]),
                               pull_by_load=False)
        assign, warm = ref.run(np.asarray(events))
        return _RefState(ref), (assign, warm)


def state_unchanged(sut):
    """A step that returns its state unchanged."""
    def broken(state, events):
        return state, sut(state, events)[1]
    return broken


def half_the_batch(sut):
    """Half of each burst left out; its answers are the empty answer."""
    def broken(state, events):
        keep = len(events) - len(events) // 2
        state, (a, w) = sut(state, events[:keep])
        pad = len(events) - keep
        return state, (np.concatenate([np.asarray(a), np.full(pad, -1, np.int32)]),
                       np.concatenate([np.asarray(w), np.zeros(pad, bool)]))
    return broken


def answer_altered(sut):
    """The first decision of each burst names the next worker."""
    def broken(state, events):
        state, (a, w) = sut(state, events)
        a = np.array(a)
        idx = np.flatnonzero(np.asarray(events)[:, 0] == ARRIVAL)
        if idx.size:
            a[idx[0]] = (a[idx[0]] + 1) % np.asarray(state[1]).shape[0]
        return state, (a, w)
    return broken


FAULTS = {"state_unchanged": state_unchanged, "half_the_batch": half_the_batch,
          "answer_altered": answer_altered}


def main(argv=None) -> int:
    import argparse

    from harness import resolve, run_cell, use_checkout

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[2]
    cell = resolve(root, args.workload)
    use_checkout(root)
    for seed in args.seeds:
        result = run_cell(cell, seed, args.seconds, False, time.perf_counter(),
                          root / ".chipbench" / "trace", sut=ControlSUT())
        print(json.dumps({"control": args.workload, "seed": seed, **result}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
