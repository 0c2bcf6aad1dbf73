"""Seeded traffic for a pull-dispatch deployment: one general generator.

A deployment (``configs/<name>.json``) is a cluster of ``workers`` with a
per-worker memory pool, a function catalogue (popularity weights, Table-I
warm/cold service times, memory per instance), closed-loop virtual users
(VUs) with uniform think times, lognormal service fluctuation and an idle
keep-alive.  A traffic mix (``traffic/<name>.json``) says how the
dispatcher batches the events: each call carries ``arrivals_per_call``
ARRIVALs, with the FINISH and EVICT events that came before them, and one
replayed segment holds ``calls_per_segment`` calls.

:func:`make_segment` returns the start state and one segment of bursts:

* **Start state** -- the steady load the model implies, drawn directly from
  the seed rather than by simulating the ramp from an empty cluster.  Each
  VU is placed in its stationary cycle (length-biased, uniform position
  within it): in service with the time it has left, or thinking.  Running
  tasks are spread over the workers as least-connections leaves them (at
  most one apart); one that its worker's pool cannot hold waits for memory.
  Each function keeps as many instances as its concurrency reaches within
  one keep-alive, a Poisson high quantile of its mean concurrency; those
  not running sit idle on a uniformly drawn worker, or the next one whose
  pool holds them (else nowhere), with ages uniform over the keep-alive.
* **Calls** -- a dispatcher must answer an ARRIVAL before the task can
  start, while FINISH and EVICT notices can wait for the next decision.
  So a call closes at its ``arrivals_per_call``-th ARRIVAL.  Every call of
  every seed carries the same number of decisions; the burst lengths, and
  so the programs the system under test compiles per length, vary a little
  with the seed.
* **Segment** -- the model run forward from that state, its events in time
  order, cut into calls.  An ARRIVAL is decided by the
  plain reference (``reference.PullDispatch``) when it is batched; the task
  starts when its call returns (at the call's last event), warm on
  the pulled instance or cold on a new one.  A cold task waits, first come
  first served, until the worker's busy instances leave room for it in the
  pool; the worker then evicts its least-recently-used idle instances
  (EVICT, handed over before the next event) until it fits.  A task ends
  after its base service time times a mean-1 lognormal (FINISH, its
  instance enqueued idle), then the VU thinks and submits again.  An idle
  instance unused for the keep-alive is evicted (EVICT).

The time spent in the reference while the segment is made is counted
apart (``stats["reference_s"]``): it is the reference's, not set-up.

The stream is causally consistent: a FINISH names the worker its task was
given in an earlier call, and an EVICT an instance that is idle.  The
reference's answers come with it, as the expected output of every burst.
"""

from __future__ import annotations

import dataclasses
import heapq
import time
from collections import deque
from statistics import NormalDist

import numpy as np

from reference import ARRIVAL, EVICT, FINISH, PullDispatch

_SUBMIT, _COMPLETE, _EXPIRE = 0, 1, 2
_LENGTH_CAP_S = 4.0  # cycle lengths are capped here for the length bias


@dataclasses.dataclass
class Segment:
    """Start state, bursts and the reference's answers for one segment."""

    idle: np.ndarray     # (F, W) int32 start state
    conns: np.ndarray    # (W,) int32
    events: np.ndarray   # (N, 3) int32, all bursts back to back
    bounds: np.ndarray   # (C + 1,) call b hands over events[bounds[b]:bounds[b+1]]
    assign: np.ndarray   # (N,) int32 reference answers (-1 unless ARRIVAL)
    warm: np.ndarray     # (N,) bool
    stats: dict

    @property
    def n_calls(self) -> int:
        return len(self.bounds) - 1

    def burst(self, b: int) -> np.ndarray:
        return self.events[self.bounds[b]:self.bounds[b + 1]]


def catalogue(config: dict):
    """``(weights, warm_s, cold_s, mem_mb)`` arrays of the catalogue."""
    fs = config["functions"]
    p = np.array([f["weight"] for f in fs], np.float64)
    return (p / p.sum(), np.array([f["warm_ms"] for f in fs]) / 1e3,
            np.array([f["cold_ms"] for f in fs]) / 1e3,
            np.array([f["mem_mb"] for f in fs], np.float64))


def offered_load(config: dict, delay_s: float = 0.0) -> dict:
    """Mean arrivals/s and concurrency per function at steady state, all
    warm, each task starting ``delay_s`` after its arrival (the model's own
    fixed point)."""
    p, warm_s, _, _ = catalogue(config)
    lo, hi = config["think_s"]
    n_vus = config["vus"]
    cycle = float(p @ warm_s) + 0.5 * (lo + hi) + delay_s
    rate = n_vus / cycle
    return {"arrivals_per_s": rate, "concurrency": rate * p * warm_s}


def _rng(seed: int) -> np.random.Generator:
    return np.random.default_rng(int(seed) % 2**64)


class _Draws:
    """Block-drawn variates, handed out one at a time."""

    def __init__(self, rng, p, sigma, think):
        self.rng, self.p, self.sigma, self.think = rng, p, sigma, think
        self._f = self._ln = self._t = iter(())

    def func(self) -> int:
        try:
            return next(self._f)
        except StopIteration:
            self._f = iter(self.rng.choice(len(self.p), 4096, p=self.p).tolist())
            return next(self._f)

    def fluct(self) -> float:
        try:
            return next(self._ln)
        except StopIteration:
            s = self.sigma
            self._ln = iter(self.rng.lognormal(-0.5 * s * s, s, 4096).tolist())
            return next(self._ln)

    def think_s(self) -> float:
        try:
            return next(self._t)
        except StopIteration:
            self._t = iter(self.rng.uniform(*self.think, 4096).tolist())
            return next(self._t)


class _Timed:
    """The reference, with the seconds spent in it counted: they are the
    reference's, not the set-up's."""

    def __init__(self, ref: PullDispatch):
        self.ref, self.seconds = ref, 0.0

    def __getattr__(self, name):
        call = getattr(self.ref, name)

        def timed(*args):
            t0 = time.perf_counter()
            try:
                return call(*args)
            finally:
                self.seconds += time.perf_counter() - t0
        return timed


def _stationary_vus(rng, n_vus, p, warm_s, sigma, think):
    """Each VU's function, whether it is in service, and the seconds left
    in its current phase, drawn from the stationary (length-biased) cycle."""
    func = np.empty(n_vus, np.int64)
    serv = np.empty(n_vus)
    cycle = np.empty(n_vus)
    todo = np.arange(n_vus)
    while todo.size:
        n = todo.size
        f = rng.choice(len(p), n, p=p)
        s = warm_s[f] * rng.lognormal(-0.5 * sigma * sigma, sigma, n)
        c = np.minimum(s + rng.uniform(*think, n), _LENGTH_CAP_S)
        ok = rng.random(n) * _LENGTH_CAP_S < c
        func[todo[ok]], serv[todo[ok]], cycle[todo[ok]] = f[ok], s[ok], c[ok]
        todo = todo[~ok]
    x = rng.random(n_vus) * cycle
    busy = x < serv
    left = np.where(busy, serv - x, cycle - x)
    return func, busy, left


def make_segment(config: dict, traffic: dict, seed: int) -> Segment:
    """The start state and one segment of bursts for ``(config, traffic,
    seed)``; the same arguments give the same segment."""
    rng = _rng(seed)
    p, warm_s, cold_s, mem = catalogue(config)
    n_funcs, n_workers = len(p), config["workers"]
    n_vus = config["vus"]
    sigma, think = config["exec_sigma"], tuple(config["think_s"])
    keep_alive, pool = config["keep_alive_s"], config["pool_mb"]
    per_call, n_calls = traffic["arrivals_per_call"], traffic["calls_per_segment"]
    # nothing is scheduled past twice the segment's expected span; a stream
    # that ran dry before its last call filled would raise below
    horizon = 2.0 * per_call * n_calls / offered_load(config)["arrivals_per_s"]

    # ---- start state: running tasks ----------------------------------------
    vfunc, busy, left = _stationary_vus(rng, n_vus, p, warm_s, sigma, think)
    run_vu = np.flatnonzero(busy)
    run_vu = run_vu[rng.permutation(run_vu.size)]
    worker_order = rng.permutation(n_workers)
    run_w = worker_order[np.arange(run_vu.size) % n_workers]
    conns = np.bincount(run_w, minlength=n_workers).astype(np.int32)
    busy_f = np.bincount(vfunc[run_vu], minlength=n_funcs)

    # a task runs where the pool holds it, and waits for memory otherwise
    busy_mem = [0.0] * n_workers
    pending = [deque() for _ in range(n_workers)]  # w -> tasks waiting for memory
    in_service = []
    for vu, w in zip(run_vu.tolist(), run_w.tolist()):
        f = int(vfunc[vu])
        if pending[w] or busy_mem[w] + mem[f] > pool:
            pending[w].append(vu)
        else:
            busy_mem[w] += mem[f]
            in_service.append((vu, w))

    # ---- start state: idle instances ----------------------------------------
    conc = offered_load(config)["concurrency"]
    looks = np.maximum(keep_alive / warm_s, 2.0)  # independent looks per keep-alive
    z = np.array([NormalDist().inv_cdf(1.0 - 1.0 / n) for n in looks])
    keep = np.ceil(conc + z * np.sqrt(conc)).astype(np.int64)
    n_idle = np.maximum(keep - busy_f, 0)
    idle_f = np.repeat(np.arange(n_funcs), n_idle)
    idle_f = idle_f[rng.permutation(idle_f.size)]
    first_w = rng.integers(0, n_workers, idle_f.size)
    idle_age = rng.uniform(0.0, keep_alive, idle_f.size)
    idle_mem = [0.0] * n_workers
    placed = []  # (f, w, age) of the instances that fit a pool
    for f, w0, age in zip(idle_f.tolist(), first_w.tolist(), idle_age.tolist()):
        for k in range(n_workers):  # the drawn worker, else the next with room
            w = (w0 + k) % n_workers
            if not pending[w] and busy_mem[w] + idle_mem[w] + mem[f] <= pool:
                idle_mem[w] += mem[f]
                placed.append((f, w, age))
                break
    idle = np.zeros((n_funcs, n_workers), np.int32)
    for f, w, _ in placed:
        idle[f, w] += 1

    t0 = time.perf_counter()
    ref = _Timed(PullDispatch(idle, conns))
    ref.seconds = time.perf_counter() - t0
    pools = [dict() for _ in range(n_workers)]  # w -> {f: [last_used asc]}
    heap = []  # (time, seq, what, a, b)
    seq = 0
    for f, w, age in sorted(placed, key=lambda x: -x[2]):  # oldest first per list
        pools[w].setdefault(f, []).append(-age)
        if keep_alive - age < horizon:
            heap.append((keep_alive - age, seq, _EXPIRE, w, f))
            seq += 1
    for vu, w in in_service:
        if left[vu] < horizon:
            heap.append((float(left[vu]), seq, _COMPLETE, vu, w))
            seq += 1
    for vu in np.flatnonzero(~busy & (left < horizon)).tolist():
        heap.append((float(left[vu]), seq, _SUBMIT, vu, -1))
        seq += 1
    heapq.heapify(heap)
    running = {vu: int(f) for vu, f in zip(run_vu.tolist(), vfunc[run_vu].tolist())}
    start_idle, start_conns = ref.state()

    draws = _Draws(rng, p, sigma, think)
    mem_l, warm_l, cold_l = mem.tolist(), warm_s.tolist(), cold_s.tolist()
    events, assign, warm = [], [], []
    bounds = [0]
    starting = []        # (vu, f, w, warm) batched in the burst just closed
    pool_evicts = deque()  # (f, w) chosen when a cold task starts
    n_keepalive_evicts = n_pool_evicts = n_waits = 0
    t = 0.0

    def emit(kind, f, w, a=-1, pulled=False):
        events.append((kind, f, w))
        assign.append(a)
        warm.append(pulled)

    def evict(f, w):
        ref.evict(f, w)
        emit(EVICT, f, w)

    def complete_at(done, vu, w):
        nonlocal seq
        if done < horizon:
            heapq.heappush(heap, (done, seq, _COMPLETE, vu, w))
            seq += 1

    def start_waiting(w):
        """Start the tasks waiting at ``w``, first come first served, cold,
        each once the pool holds it after evicting least-recently-used idle
        instances."""
        nonlocal n_pool_evicts
        while pending[w]:
            f = running[pending[w][0]]
            if busy_mem[w] + mem_l[f] > pool:
                return  # waits until a task of w ends
            while busy_mem[w] + idle_mem[w] + mem_l[f] > pool:
                g = min(pools[w], key=lambda g: pools[w][g][0])  # LRU
                lst = pools[w][g]
                lst.pop(0)
                if not lst:
                    del pools[w][g]
                idle_mem[w] -= mem_l[g]
                pool_evicts.append((g, w))
                n_pool_evicts += 1
            busy_mem[w] += mem_l[f]
            complete_at(t + cold_l[f] * draws.fluct(), pending[w].popleft(), w)

    for w in range(n_workers):
        start_waiting(w)
    for _ in range(n_calls):
        # tasks batched in the last call start now that it returned
        for vu, f, w, pulled in starting:
            if pulled:
                complete_at(t + warm_l[f] * draws.fluct(), vu, w)
            else:
                n_waits += int(bool(pending[w]) or busy_mem[w] + mem_l[f] > pool)
                pending[w].append(vu)
                start_waiting(w)
        starting = []
        while len(starting) < per_call:
            if pool_evicts:  # handed over before anything that follows
                evict(*pool_evicts.popleft())
                continue
            if not heap:
                raise RuntimeError("the model ran out of events before the "
                                   "segment's last call filled")
            t, _, what, a, b = heapq.heappop(heap)
            if what == _SUBMIT:
                f = draws.func()
                w, pulled = ref.arrival(f)
                if pulled:
                    lst = pools[w][f]
                    lst.pop()  # the newest idle instance is reused
                    if not lst:
                        del pools[w][f]
                    idle_mem[w] -= mem_l[f]
                    busy_mem[w] += mem_l[f]
                running[a] = f
                starting.append((a, f, w, pulled))
                emit(ARRIVAL, f, -1, w, pulled)
            elif what == _COMPLETE:
                vu, w = a, b
                f = running.pop(vu)
                busy_mem[w] -= mem_l[f]
                pools[w].setdefault(f, []).append(t)
                idle_mem[w] += mem_l[f]
                ref.finish(f, w)
                emit(FINISH, f, w)
                if t + keep_alive < horizon:
                    heapq.heappush(heap, (t + keep_alive, seq, _EXPIRE, w, f))
                    seq += 1
                nxt = t + draws.think_s()
                if nxt < horizon:
                    heapq.heappush(heap, (nxt, seq, _SUBMIT, vu, -1))
                    seq += 1
                start_waiting(w)
            else:  # _EXPIRE: the oldest instance of (w, f) past its keep-alive
                w, f = a, b
                lst = pools[w].get(f)
                if lst and t - lst[0] >= keep_alive - 1e-9:
                    lst.pop(0)
                    if not lst:
                        del pools[w][f]
                    idle_mem[w] -= mem_l[f]
                    evict(f, w)
                    n_keepalive_evicts += 1
                    if lst and t - lst[0] >= keep_alive - 1e-9:  # the next one
                        heapq.heappush(heap, (t, seq, _EXPIRE, w, f))
                        seq += 1
        bounds.append(len(events))

    ev = np.array(events, np.int32).reshape(-1, 3)
    kinds = np.bincount(ev[:, 0], minlength=3)
    sizes = np.diff(bounds)
    stats = {
        "calls": len(sizes), "virtual_s": t, "events": int(ev.shape[0]),
        "arrivals": int(kinds[ARRIVAL]), "finishes": int(kinds[FINISH]),
        "evicts": int(kinds[EVICT]), "pool_evicts": n_pool_evicts,
        "keepalive_evicts": n_keepalive_evicts, "memory_waits": n_waits,
        "warm": int(np.sum(warm)), "burst_min": int(sizes.min()),
        "burst_max": int(sizes.max()), "start_running": int(run_vu.size),
        "start_idle": int(start_idle.sum()), "reference_s": ref.seconds,
    }
    return Segment(start_idle, start_conns, ev, np.array(bounds, np.int64),
                   np.array(assign, np.int32), np.array(warm, bool), stats)
