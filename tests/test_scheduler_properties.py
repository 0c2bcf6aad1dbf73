"""Hypothesis property tests: Algorithm-1 object == JAX array formulation,
and the fused dispatch == the scan it fuses.

Separate from test_scheduler.py so the deterministic scheduler tests still
run on environments without hypothesis (this module is skipped there)."""

import runpy
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dep: skip only the property tests
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.core import (  # noqa: E402
    ARRIVAL,
    FINISH,
    HikuScheduler,
    JIQState,
    init_state,
    jax_sched,
    sched_many,
    sched_many_fused,
)
from repro.kernels import sched_step  # noqa: E402

# the chip benchmark's plain reference: Algorithm 1 by whole-array scans,
# importing nothing of the program
brute_force = runpy.run_path(
    str(Path(__file__).resolve().parents[1] / "benchmarks" / "chip" / "reference.py")
)["brute_force"]


class _FirstChoice:
    """Deterministic stand-in for random.Random: always pick first/lowest.

    The least-connections fallback draws ``randrange(t)`` over its ``t``
    tied workers in ascending id order, so 0 is the lowest tied id — the
    tie the JAX scan breaks."""

    def choice(self, xs):
        return min(xs)

    def randrange(self, n):
        return 0


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), n_events=st.integers(1, 60),
       F=st.integers(1, 5), W=st.integers(1, 6))
def test_jax_sched_equivalent_to_python(seed, n_events, F, W):
    """Deterministic-tie-break JIQ: array formulation == Algorithm 1 object."""
    rng = np.random.default_rng(seed)
    py = HikuScheduler(W, seed=0)
    py.rng = _FirstChoice()
    state = init_state(F, W)
    events = []
    running = []  # (worker, func) active
    for _ in range(n_events):
        kind = rng.choice([ARRIVAL, FINISH]) if running else ARRIVAL
        if kind == ARRIVAL:
            f = int(rng.integers(0, F))
            events.append((ARRIVAL, f, -1))
        else:
            w, f = running.pop(int(rng.integers(0, len(running))))
            events.append((FINISH, f, w))
        # drive python scheduler
        k, f, w = events[-1]
        if k == ARRIVAL:
            wpy = py.schedule(str(f))
            running.append((wpy, f))
            events[-1] = (ARRIVAL, f, -1, wpy)  # remember for the check
        else:
            py.on_finish(w, str(f))
            events[-1] = (FINISH, f, w, -1)
    ev_arr = jnp.array([(k, f, w) for (k, f, w, _) in events], jnp.int32)
    state, (ws, warm) = sched_many(state, ev_arr, key=None)
    for i, (k, f, w, wpy) in enumerate(events):
        if k == ARRIVAL:
            assert int(ws[i]) == wpy, f"event {i}: jax={int(ws[i])} py={wpy}"
    # final connection counts agree
    np.testing.assert_array_equal(
        np.asarray(state.conns), np.array([py.conns[w] for w in range(W)])
    )


@pytest.mark.parametrize("table", ["vmem", "hbm"])
@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), n=st.integers(0, 120), chunk=st.integers(2, 64),
       F=st.integers(1, 8), W=st.integers(1, 12))
def test_fused_dispatch_bitwise_equals_scan(table, seed, n, chunk, F, W):
    """Whatever the chunk (so wherever the launches split the stream), the
    table's form and the start state, ``sched_many_fused`` (interpret mode)
    is bitwise the event-by-event scan and the benchmark's plain reference,
    on mixed streams of all four kinds (3 is a no-op), the empty one
    included."""
    rng = np.random.default_rng(seed)
    kinds = rng.integers(0, 4, n)
    ev = np.stack([kinds, rng.integers(0, F, n),
                   np.where(kinds == ARRIVAL, -1, rng.integers(0, W, n))], 1).astype(np.int32)
    idle, conns = rng.integers(0, 3, (F, W)), rng.integers(0, 5, W)
    state = JIQState(jnp.asarray(idle, jnp.int32), jnp.asarray(conns, jnp.int32))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax_sched, "CHUNK", chunk)
        if table == "hbm":  # interpret mode holds the HBM form to no cap
            mp.setattr(sched_step, "VMEM_CAP_BYTES", sched_step.vmem_bytes(F, W) - 1)
        assert sched_step.table_form("test", (F, W), interpret=True) == table
        end, (ws, warm) = sched_many_fused(state, ev, interpret=True)
    fused = [np.asarray(x) for x in (ws, warm, end.idle, end.conns)]
    s1, (ws1, warm1) = sched_many(state, jnp.asarray(ev))
    for got, want in zip(fused, (ws1, warm1, s1.idle, s1.conns)):
        assert got.dtype == np.asarray(want).dtype and np.array_equal(got, np.asarray(want))
    for got, want in zip(fused, brute_force(idle, conns, ev)):
        assert np.array_equal(got, want)
