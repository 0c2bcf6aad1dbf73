"""Hypothesis property test: Algorithm-1 object == JAX array formulation.

Separate from test_scheduler.py so the deterministic scheduler tests still
run on environments without hypothesis (this module is skipped there)."""

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # optional dep: skip only the property tests
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.core import ARRIVAL, FINISH, HikuScheduler, init_state, sched_many  # noqa: E402


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
