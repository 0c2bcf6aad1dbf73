"""Plain reference of pull-based dispatch (Algorithm 1 of the Hiku paper).

Independent of the code under test: it imports nothing of the program and
reads only the start state and the event stream the benchmark made.

State, as the dispatcher sees it:

* ``idle[f][w]`` -- how many idle instances of function ``f`` worker ``w``
  has enqueued in ``PQ_f`` (one entry per instance);
* ``conns[w]`` -- the active connections of worker ``w``, the priority key.

Events are ``(kind, func, worker)`` triples:

* ARRIVAL ``(0, f, -1)``: if ``PQ_f`` holds a worker, dequeue the one with
  the fewest connections, lowest id on ties (a warm *pull*); otherwise take
  the worker with the fewest connections overall, lowest id on ties (the
  *fallback*).  The chosen worker gains a connection.  Answer: the worker
  and whether it was pulled.
* FINISH ``(1, f, w)``: ``w`` enqueues one idle ``f`` instance and loses a
  connection (never below 0).
* EVICT ``(2, f, w)``: ``w`` drops one idle ``f`` instance, if it has one.
* any other kind changes nothing.

:class:`PullDispatch` keeps ``PQ_f`` as lazy binary heaps and the fallback
as buckets of workers by connection count, so each event costs O(log W) in
Python; :func:`brute_force` is the same semantics as whole-array scans, the
second witness the tests hold it against.
"""

from __future__ import annotations

import heapq

import numpy as np

ARRIVAL, FINISH, EVICT = 0, 1, 2


class PullDispatch:
    """Algorithm 1 over ``idle`` ``(F, W)`` and ``conns`` ``(W,)`` counts.

    ``pull_by_load=False`` breaks the guarantee that a pull takes the
    least-loaded holder: it takes the lowest-id holder instead.  That is the
    benchmark's control, never its reference."""

    def __init__(self, idle: np.ndarray, conns: np.ndarray, pull_by_load: bool = True):
        n_funcs, n_workers = idle.shape
        self.pull_by_load = pull_by_load
        self.conns = [int(c) for c in conns]
        self.idle = [dict() for _ in range(n_funcs)]   # f -> {w: count}
        self.total = [0] * n_funcs
        self.heaps = [[] for _ in range(n_funcs)]      # f -> [(key, w)]
        self.funcs_of = [set() for _ in range(n_workers)]
        fs, ws = np.nonzero(idle)
        for f, w in zip(fs.tolist(), ws.tolist()):
            n = int(idle[f, w])
            self.idle[f][w] = n
            self.total[f] += n
            self.funcs_of[w].add(f)
            self.heaps[f].extend([(self._key(w), w)] * n)
        for h in self.heaps:
            heapq.heapify(h)
        # fallback: workers bucketed by connection count (lazy: an entry is
        # live while the worker's count still equals the bucket's)
        self.n_at = {}
        self.bucket = {}
        for w, c in enumerate(self.conns):
            self.n_at[c] = self.n_at.get(c, 0) + 1
            self.bucket.setdefault(c, []).append(w)  # ascending ids: a heap

    def _key(self, w: int) -> int:
        return self.conns[w] if self.pull_by_load else 0

    # ----------------------------------------------------------- connections
    def _set_conns(self, w: int, c: int) -> None:
        old = self.conns[w]
        if c == old:
            return
        self.conns[w] = c
        self.n_at[old] -= 1
        self.n_at[c] = self.n_at.get(c, 0) + 1
        heapq.heappush(self.bucket.setdefault(c, []), w)
        if self.pull_by_load and c < old:
            # a lower key must be visible in every queue holding w
            for f in self.funcs_of[w]:
                heapq.heappush(self.heaps[f], (c, w))

    def _least_connections(self) -> int:
        c = 0
        while not self.n_at.get(c):
            c += 1
        b = self.bucket[c]
        while self.conns[b[0]] != c:
            heapq.heappop(b)
        return b[0]

    # ---------------------------------------------------------------- queues
    def _dequeue(self, f: int) -> int:
        heap, counts = self.heaps[f], self.idle[f]
        if len(heap) > 64 and len(heap) > 8 * self.total[f]:
            heap[:] = [(self._key(w), w) for w, n in counts.items() for _ in range(n)]
            heapq.heapify(heap)
        while True:
            key, w = heap[0]
            if w not in counts:
                heapq.heappop(heap)               # no longer enqueued
            elif key != self._key(w):
                heapq.heapreplace(heap, (self._key(w), w))  # stale key
            else:
                heapq.heappop(heap)
                self._drop(f, w)
                return w

    def _drop(self, f: int, w: int) -> None:
        counts = self.idle[f]
        n = counts[w] - 1
        if n:
            counts[w] = n
        else:
            del counts[w]
            self.funcs_of[w].discard(f)
        self.total[f] -= 1

    # ---------------------------------------------------------------- events
    def arrival(self, f: int) -> tuple[int, bool]:
        warm = self.total[f] > 0
        w = self._dequeue(f) if warm else self._least_connections()
        self._set_conns(w, self.conns[w] + 1)
        if warm and w in self.idle[f]:
            # w stays in PQ_f under its new key
            heapq.heappush(self.heaps[f], (self._key(w), w))
        return w, warm

    def finish(self, f: int, w: int) -> None:
        self._set_conns(w, max(self.conns[w] - 1, 0))
        counts = self.idle[f]
        counts[w] = counts.get(w, 0) + 1
        self.total[f] += 1
        self.funcs_of[w].add(f)
        heapq.heappush(self.heaps[f], (self._key(w), w))

    def evict(self, f: int, w: int) -> None:
        if w in self.idle[f]:
            self._drop(f, w)

    def apply(self, kind: int, f: int, w: int) -> tuple[int, bool]:
        """One event; returns ``(worker, warm)``, ``(-1, False)`` unless an
        ARRIVAL."""
        if kind == ARRIVAL:
            return self.arrival(f)
        if kind == FINISH:
            self.finish(f, w)
        elif kind == EVICT:
            self.evict(f, w)
        return -1, False

    def run(self, events: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """Answers ``(assign (N,) int32, warm (N,) bool)`` for ``(N, 3)``
        events, applied in order."""
        out = [self.apply(k, f, w) for k, f, w in events.tolist()]
        assign = np.array([a for a, _ in out], np.int32).reshape(-1)
        warm = np.array([b for _, b in out], bool).reshape(-1)
        return assign, warm

    def state(self) -> tuple[np.ndarray, np.ndarray]:
        idle = np.zeros((len(self.idle), len(self.conns)), np.int32)
        for f, counts in enumerate(self.idle):
            for w, n in counts.items():
                idle[f, w] = n
        return idle, np.array(self.conns, np.int32)


def brute_force(idle, conns, events):
    """The same semantics by whole-array scans, one event at a time.
    Returns ``(assign, warm, idle', conns')``."""
    idle = np.array(idle, np.int64)
    conns = np.array(conns, np.int64)
    n = len(events)
    assign = np.full(n, -1, np.int32)
    warm = np.zeros(n, bool)
    for i, (k, f, w) in enumerate(np.asarray(events).tolist()):
        if k == ARRIVAL:
            holders = idle[f] > 0
            if holders.any():
                pick = int(np.argmin(np.where(holders, conns, np.iinfo(np.int64).max)))
                idle[f, pick] -= 1
                warm[i] = True
            else:
                pick = int(np.argmin(conns))
            conns[pick] += 1
            assign[i] = pick
        elif k == FINISH:
            idle[f, w] += 1
            conns[w] = max(conns[w] - 1, 0)
        elif k == EVICT and idle[f, w] > 0:
            idle[f, w] -= 1
    return assign, warm, idle.astype(np.int32), conns.astype(np.int32)


def state_after(idle, conns, events, assign, warm):
    """The state that a causally consistent stream leaves, from its events
    and the reference's answers: every FINISH names a worker with a
    connection, every EVICT an instance that is enqueued, so the changes
    add up without clipping."""
    idle = np.array(idle, np.int64)
    conns = np.array(conns, np.int64)
    ev = np.asarray(events)
    kind, f, w = ev[:, 0], ev[:, 1], ev[:, 2]
    arr = kind == ARRIVAL
    np.add.at(conns, assign[arr], 1)
    pulled = arr & warm
    np.add.at(idle, (f[pulled], assign[pulled]), -1)
    fin = kind == FINISH
    np.add.at(idle, (f[fin], w[fin]), 1)
    np.add.at(conns, w[fin], -1)
    evt = kind == EVICT
    np.add.at(idle, (f[evt], w[evt]), -1)
    return idle.astype(np.int32), conns.astype(np.int32)
