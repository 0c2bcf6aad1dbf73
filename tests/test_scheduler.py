"""Scheduler semantics: Algorithm 1, baselines, and the JAX formulation.

The hypothesis-based python<->jax equivalence property test lives in
test_scheduler_properties.py so this module runs without hypothesis."""

import runpy
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ARRIVAL,
    EVICT,
    FINISH,
    HikuScheduler,
    available_schedulers,
    init_state,
    make_scheduler,
    sched_many,
    sched_step,
)


def test_registry_has_paper_baselines():
    have = set(available_schedulers())
    assert {"hiku", "ch", "ch_bl", "rj_ch", "least_connections", "random"} <= have


class _FirstChoice:
    """Deterministic stand-in for random.Random: always pick first/lowest."""

    def choice(self, xs):
        return min(xs)

    def randrange(self, n):
        return 0  # tie index 0 == lowest tied worker id


def test_hiku_algorithm1_semantics():
    s = HikuScheduler(3, seed=0)
    s.rng = _FirstChoice()
    # no idle instances -> fallback least-connections (all zero -> worker 0)
    w = s.schedule("f1")
    assert w == 0 and s.conns[0] == 1
    # finish -> pull enqueue into PQ_f1
    s.on_finish(0, "f1")
    assert s.queue_depth("f1") == 1
    # next request for f1 MUST be pulled from the queue (warm)
    w = s.schedule("f1")
    assert w == 0 and s.queue_depth("f1") == 0
    # requests for other functions do not touch PQ_f1 (fallback path instead)
    s.on_finish(0, "f1")
    w2 = s.schedule("f2")
    assert s.queue_depth("f1") == 1  # PQ_f1 untouched by the f2 request
    assert w2 == 0  # LC tie-break: deterministic stub picks lowest index


def test_hiku_dequeues_least_loaded():
    s = HikuScheduler(3, seed=0)
    s.rng = _FirstChoice()
    # enqueue workers 1 and 2 with different loads (pull signals decrement
    # the connection count, so pre-load one extra connection each)
    for w, c in ((1, 6), (2, 3)):
        for _ in range(c):
            s.on_assign(w, "f")
    s.on_finish(1, "f")  # conns: {0: 0, 1: 5, 2: 3}; PQ_f = {1}
    s.on_finish(2, "f")  # conns: {0: 0, 1: 5, 2: 2}; PQ_f = {1, 2}
    w = s.schedule("f")
    assert w == 2  # least-loaded enqueued worker, NOT global least-loaded (0)


def test_hiku_eviction_notification():
    s = HikuScheduler(2, seed=0)
    s.on_finish(1, "f")
    s.on_finish(1, "f")
    assert s.queue_depth("f") == 2
    s.on_evict(1, "f")  # removes FIRST occurrence only (Algorithm 1 l.19)
    assert s.queue_depth("f") == 1


def test_hiku_worker_removal_purges_queues():
    s = HikuScheduler(3, seed=0)
    s.on_finish(2, "a")
    s.on_finish(2, "b")
    s.on_worker_removed(2)
    assert s.queue_depth() == 0
    assert all(s.schedule(f) != 2 for f in ("a", "b", "c"))


def test_ch_locality_and_stability():
    s = make_scheduler("ch", 5, seed=1)
    w1 = [s.select("func-x") for _ in range(10)]
    assert len(set(w1)) == 1  # perfect locality
    # removing an unrelated worker must not remap func-x (consistency)
    target = w1[0]
    other = (target + 1) % 5
    s.on_worker_removed(other)
    assert s.select("func-x") == target


def test_chbl_respects_bound():
    s = make_scheduler("ch_bl", 4, seed=0, threshold=1.25)
    target = s.ring.lookup("hot")
    s.conns = {w: 0 for w in s.workers}
    s.conns[target] = 10  # overloaded far beyond bound
    w = s.select("hot")
    assert w != target  # spills to next non-overloaded clockwise


def test_jax_sched_evict():
    state = init_state(2, 3)
    ev = jnp.array([
        (ARRIVAL, 0, -1),  # cold -> worker 0
        (FINISH, 0, 0),    # enqueue PQ_0 <- w0
        (EVICT, 0, 0),     # notification removes it
        (ARRIVAL, 0, -1),  # must be cold again
    ], jnp.int32)
    state, (ws, warm) = sched_many(state, ev)
    assert not bool(warm[3])
    assert int(state.idle.sum()) == 0


@pytest.mark.parametrize("as_array", [np.asarray, jnp.asarray], ids=["numpy", "jnp"])
@pytest.mark.parametrize("n", [1, 63, 64, 65, 160])
def test_sched_many_fused_matches_scan(n, as_array, monkeypatch):
    """Chunked fused dispatch (interpret mode) == event-by-event scan, at
    stream lengths on both sides of a 64-event chunk's boundaries, from
    a host or a device array.  W = 9 runs the VMEM form's lane padding and
    its +INF connection mask."""
    from repro.core import jax_sched, sched_many_fused

    monkeypatch.setattr(jax_sched, "CHUNK", 64)
    rng = np.random.default_rng(5)
    state = init_state(6, 9)
    events = []
    for _ in range(n):
        k = int(rng.integers(0, 3))
        events.append((k, int(rng.integers(0, 6)), -1 if k == ARRIVAL else int(rng.integers(0, 9))))
    ev = np.array(events, np.int32)
    s1, (ws1, warm1) = sched_many(state, jnp.asarray(ev))
    s2, (ws2, warm2) = sched_many_fused(state, as_array(ev), interpret=True)
    assert ws2.shape == (n,) and ws2.dtype == jnp.int32 and warm2.dtype == bool
    assert jnp.all(ws1 == ws2) and jnp.all(warm1 == warm2)
    assert jnp.all(s1.idle == s2.idle) and jnp.all(s1.conns == s2.conns)
    # off the TPU the native kernel raises, naming the backend it found
    with pytest.raises(RuntimeError, match=repr(jax.default_backend())):
        sched_many_fused(state, as_array(ev))


# the chip benchmark's plain reference: Algorithm 1 by whole-array scans,
# importing nothing of the program
brute_force = runpy.run_path(
    str(Path(__file__).resolve().parents[1] / "benchmarks" / "chip" / "reference.py")
)["brute_force"]


@pytest.mark.parametrize("F,W", [(1, 5), (24, 130), (40, 200)])
@pytest.mark.parametrize("n", [1, 63, 64, 65, 160])
def test_sched_many_fused_hbm_matches_scan(n, F, W, monkeypatch):
    """The HBM form through ``sched_many_fused`` (interpret mode, a VMEM
    cap just below the VMEM form's need) == the event-by-event scan and the plain
    reference, bitwise, on mixed streams of all four kinds, across a
    64-event chunk's boundaries (several chunks: the first copies the
    table, the rest update it in place) and with the state carried into a
    second call."""
    from repro.core import JIQState, jax_sched, sched_many_fused
    from repro.kernels import sched_step

    monkeypatch.setattr(jax_sched, "CHUNK", 64)
    monkeypatch.setattr(sched_step, "VMEM_CAP_BYTES", sched_step.vmem_bytes(F, W) - 1)
    rng = np.random.default_rng(n * 100 + F)
    kinds = rng.integers(0, 4, 2 * n)
    ev = np.stack([kinds, rng.integers(0, F, 2 * n),
                   np.where(kinds == ARRIVAL, -1, rng.integers(0, W, 2 * n))], 1)
    ev = ev.astype(np.int32)
    idle, conns = rng.integers(0, 3, (F, W)), rng.integers(0, 5, W)
    state = JIQState(jnp.asarray(idle, jnp.int32), jnp.asarray(conns, jnp.int32))
    mid, (ws_a, warm_a) = sched_many_fused(state, ev[:n], interpret=True)
    end, (ws_b, warm_b) = sched_many_fused(mid, ev[n:], interpret=True)
    fused = (np.concatenate([ws_a, ws_b]), np.concatenate([warm_a, warm_b]),
             np.asarray(end.idle), np.asarray(end.conns))
    s1, (ws1, warm1) = sched_many(state, jnp.asarray(ev))
    for got, want in zip(fused, (ws1, warm1, s1.idle, s1.conns)):
        assert np.array_equal(got, np.asarray(want))
    for got, want in zip(fused, brute_force(idle, conns, ev)):
        assert np.array_equal(got, want)


@pytest.mark.parametrize("table", ["vmem", "hbm"])
def test_sched_many_fused_empty_stream_changes_nothing(table, monkeypatch):
    """No events make no kernel launch, on either form: the state comes
    back bit for bit and the answers are empty, int32 and bool."""
    from repro.core import JIQState, sched_many_fused
    from repro.kernels import sched_step

    F, W = 24, 130
    if table == "hbm":
        monkeypatch.setattr(sched_step, "VMEM_CAP_BYTES", sched_step.vmem_bytes(F, W) - 1)
    assert sched_step.table_form("test", (F, W), interpret=True) == table
    rng = np.random.default_rng(12)
    idle, conns = rng.integers(0, 3, (F, W)), rng.integers(0, 5, W)
    state = JIQState(jnp.asarray(idle, jnp.int32), jnp.asarray(conns, jnp.int32))
    end, (ws, warm) = sched_many_fused(state, np.zeros((0, 3), np.int32), interpret=True)
    assert ws.shape == warm.shape == (0,)
    assert ws.dtype == jnp.int32 and warm.dtype == bool
    assert end.idle.dtype == end.conns.dtype == jnp.int32
    assert np.array_equal(end.idle, idle) and np.array_equal(end.conns, conns)


def test_least_connections_tracker_matches_ref_live(monkeypatch):
    """The bitmap-tracker fallback equals the full-scan reference on every
    call of a live run — same worker picked, same randomness consumed —
    including across a worker failure and rejoin (tracker drop/add)."""
    from repro.core import SimConfig, Simulator
    from repro.core.scheduler import Scheduler

    calls = []
    orig = Scheduler._least_connections

    def checked(self):
        before = self.rng.getstate()
        w = orig(self)
        after = self.rng.getstate()
        self.rng.setstate(before)
        assert Scheduler._least_connections_ref(self) == w
        assert self.rng.getstate() == after  # identical RNG consumption
        calls.append(w)
        return w

    monkeypatch.setattr(Scheduler, "_least_connections", checked)
    for name in ("hiku", "least_connections"):
        sim = Simulator(
            make_scheduler(name, 40, seed=3), cfg=SimConfig(n_workers=40), seed=3
        )
        sim.inject_failure(3.0, 7)
        sim.inject_worker(9.0, 7)
        sim.run(n_vus=120, duration_s=30.0)
    assert len(calls) > 50  # the fallback path was actually exercised


def test_jax_sched_random_tiebreak_uniform():
    """Fallback random tie-break covers tied workers (Algorithm 1 l.10)."""
    state = init_state(1, 4)
    ev = jnp.array([(ARRIVAL, 0, -1)], jnp.int32)
    picks = set()
    for i in range(40):
        _, (w, _) = sched_many(state, ev, key=jax.random.key(i))
        picks.add(int(w[0]))
    assert picks == {0, 1, 2, 3}
