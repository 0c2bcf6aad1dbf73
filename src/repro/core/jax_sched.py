"""Pull-based scheduling as a composable JAX module (vectorized Algorithm 1).

The control-plane scheduler in ``hiku.py`` is an event-driven Python object.
For high-throughput request streams (and to make the paper's algorithm a
first-class JAX citizen) this module expresses the *same* semantics as a pure
state-transition over arrays, scannable with ``jax.lax`` and shardable over
the worker axis:

* ``idle[f, w]``  — multiset size of worker ``w``'s entries in ``PQ_f``
  (one per enqueued idle instance).  Since ``PQ_f`` is priority-ordered by
  load, dequeuing the min-load member is ``argmin_w(conns | idle[f,w]>0)`` —
  the array form of a sorted queue; no order information is lost.
* ``conns[w]``    — active connections (the priority key of Algorithm 1).

Events are encoded as ``(kind, func, worker)`` int32 triples:
  kind 0 = ARRIVAL(func)        -> returns (worker, warm) assignment
  kind 1 = FINISH(func, worker) -> pull enqueue (Algorithm 1 l.13-16)
  kind 2 = EVICT(func, worker)  -> notification   (Algorithm 1 l.17-20)

Random tie-breaking uses the Gumbel-max trick over exact ties, matching the
"random selection from W_min" of the fallback mechanism.

``kernels/sched_step.py`` fuses mixed event bursts into one Pallas kernel
(``sched_many_fused``); ``kernels/ref.py`` points back at this module as the
oracle.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from ..utils.compile_cache import compile_count

ARRIVAL, FINISH, EVICT = 0, 1, 2
_INF = 2**30  # python int: a jnp scalar here would start a backend at import


class JIQState(NamedTuple):
    """Scheduler state in array form: the whole of Algorithm 1's bookkeeping.

    ``idle[f, w]`` is the multiset count of worker ``w``'s entries in
    ``PQ_f`` (one per enqueued idle instance); ``conns[w]`` is the active
    connection count — the priority key.  Semantically equivalent to
    ``HikuScheduler``'s object state (see module docstring)."""

    idle: jax.Array   # (F, W) int32 — PQ_f membership multiset
    conns: jax.Array  # (W,)  int32 — active connections


def init_state(n_funcs: int, n_workers: int) -> JIQState:
    """Empty :class:`JIQState` for ``n_funcs`` functions x ``n_workers``
    workers (no idle instances enqueued, zero connections)."""
    return JIQState(
        idle=jnp.zeros((n_funcs, n_workers), jnp.int32),
        conns=jnp.zeros((n_workers,), jnp.int32),
    )


def _tie_break_argmin(scores: jax.Array, key: jax.Array | None) -> jax.Array:
    """argmin with uniform random choice among exact ties (Gumbel-max)."""
    if key is None:  # deterministic mode: first index wins
        return jnp.argmin(scores)
    m = scores.min()
    tied = scores == m
    g = jax.random.gumbel(key, scores.shape)
    return jnp.argmax(jnp.where(tied, g, -jnp.inf))


def sched_step(
    state: JIQState, event: jax.Array, key: jax.Array | None = None
) -> Tuple[JIQState, Tuple[jax.Array, jax.Array]]:
    """One event transition.  Returns (state', (worker, warm)).

    For FINISH/EVICT events the returned assignment is (-1, False).
    """
    kind, func, worker = event[0], event[1], event[2]
    idle_f = state.idle[func]

    # ---- ARRIVAL: pull mechanism, else least-connections fallback ----------
    has_idle = jnp.any(idle_f > 0)
    pull_scores = jnp.where(idle_f > 0, state.conns, _INF)
    if key is not None:
        k_pull, k_fb = jax.random.split(key)
    else:
        k_pull = k_fb = None
    w_pull = _tie_break_argmin(pull_scores, k_pull)
    w_fallback = _tie_break_argmin(state.conns, k_fb)
    w_assign = jnp.where(has_idle, w_pull, w_fallback).astype(jnp.int32)

    is_arrival = kind == ARRIVAL
    is_finish = kind == FINISH
    is_evict = kind == EVICT

    # idle-queue updates
    idle = state.idle
    #   ARRIVAL dequeues (only if pulled); FINISH enqueues; EVICT removes one.
    dec_arrival = (is_arrival & has_idle).astype(jnp.int32)
    idle = idle.at[func, w_assign].add(-dec_arrival)
    idle = idle.at[func, worker].add(is_finish.astype(jnp.int32))
    idle = idle.at[func, worker].add(-(is_evict & (idle[func, worker] > 0)).astype(jnp.int32))
    idle = jnp.maximum(idle, 0)

    # connection counts
    conns = state.conns
    conns = conns.at[w_assign].add(is_arrival.astype(jnp.int32))
    conns = conns.at[worker].add(-is_finish.astype(jnp.int32))
    conns = jnp.maximum(conns, 0)

    out_worker = jnp.where(is_arrival, w_assign, jnp.int32(-1))
    out_warm = is_arrival & has_idle
    return JIQState(idle, conns), (out_worker, out_warm)


def sched_many(
    state: JIQState, events: jax.Array, key: jax.Array | None = None
) -> Tuple[JIQState, Tuple[jax.Array, jax.Array]]:
    """Scan ``sched_step`` over an (N, 3) int32 event stream."""
    n = events.shape[0]
    keys = jax.random.split(key, n) if key is not None else None

    def body(carry, xs):
        if keys is None:
            ev = xs
            return sched_step(carry, ev, None)
        ev, k = xs
        return sched_step(carry, ev, k)

    xs = events if keys is None else (events, keys)
    return jax.lax.scan(body, state, xs)


def sched_many_fused(
    state: JIQState,
    events: jax.Array,
    key: jax.Array | None = None,
    chunk: int = 1024,
    interpret: bool = False,
) -> Tuple[JIQState, Tuple[jax.Array, jax.Array]]:
    """``sched_many`` with the whole stream fused into chunked Pallas dispatches.

    Each ``chunk`` of mixed (ARRIVAL|FINISH|EVICT) events costs *one* kernel
    launch (kernels/sched_step.sched_events) instead of one scan iteration
    per event; state is carried between chunks.  The whole call is one
    jitted program (:func:`_fused_call`), compiled once per stream length
    and chunk.  Bit-exact against ``sched_many(state, events, key=None)``.

    A PRNG ``key`` asks for randomized tie-breaks, which only the scan path
    draws, so that call is ``sched_many``.  Otherwise the kernel runs
    natively, which needs the TPU: on any other backend this raises unless
    ``interpret=True`` runs the kernel body in Pallas's interpreter.

    The kernel path writes profiler spans (``jax.profiler.TraceAnnotation``;
    they cost about a microsecond each when no trace is active):
    ``sched.fused`` around the whole call, with the metadata ``chunks``,
    ``events`` (real events handed in), ``padded`` (no-op events added to
    fill the last chunk) and ``compiles`` (backend compiles during the call,
    :func:`repro.utils.compile_cache.compile_count`); and inside it, once
    each, ``sched.stage_in`` (the host's checks of the backend and the
    table's VMEM need), ``sched.launch`` (the dispatch of the one program,
    which also moves host events to the device: on the TPU host that costs
    less than a ``jax.device_put`` of them first) and ``sched.stage_out``
    (the result built from its outputs).
    """
    if key is not None:
        return sched_many(state, events, key)
    from ..kernels import ops  # deferred: ops imports models/ (via ref)

    count = compile_count()
    compiles = count.compiles
    with TraceAnnotation("sched.fused") as call:
        with TraceAnnotation("sched.stage_in"):
            ops.check_sched_events("sched_many_fused", state.idle.shape, interpret)
        with TraceAnnotation("sched.launch"):
            idle, conns, assign, warm = _fused_call(
                events, state.idle, state.conns, chunk=chunk, interpret=interpret
            )
        with TraceAnnotation("sched.stage_out"):
            out = JIQState(idle, conns), (assign, warm)
        n = events.shape[0]
        chunks = -(-n // chunk)
        call.set_metadata(chunks=chunks, events=n, padded=chunks * chunk - n,
                          compiles=count.compiles - compiles)
    return out


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _fused_call(events, idle, conns, chunk, interpret):
    """One ``sched_many_fused`` call: pad the ``(n, 3)`` stream to whole
    chunks, run the kernel chunk by chunk with the state carried, and cut
    the answers back to ``n``.  Returns ``(idle', conns', assign, warm)``."""
    from ..kernels import ops

    n = events.shape[0]
    chunks = -(-n // chunk)
    # pad the ragged last chunk with kind=3 no-op events (func/worker 0 keep
    # the row loads in bounds; an unknown kind updates nothing) so every
    # launch shares one (chunk,) kernel shape
    pad = jnp.zeros((chunks * chunk - n, 3), jnp.int32).at[:, 0].set(3)
    ev = jnp.concatenate([events, pad]).reshape(chunks, chunk, 3)

    def run(carry, e):
        a, warm, idle, conns = ops._sched_events(
            e[:, 0], e[:, 1], e[:, 2], *carry, interpret=interpret
        )
        return (idle, conns), (a, warm)

    if chunks == 1:
        (idle, conns), (a, warm) = run((idle, conns), ev[0])
    else:
        (idle, conns), (a, warm) = jax.lax.scan(run, (idle, conns), ev)
    return idle, conns, a.reshape(-1)[:n], warm.reshape(-1)[:n].astype(bool)


def sched_many_adaptive(
    state: JIQState,
    events: jax.Array,
    detector,
    densities=None,
    segment: int = 1024,
    key: jax.Array | None = None,
    interpret: bool = False,
) -> Tuple[JIQState, Tuple[jax.Array, jax.Array]]:
    """Burst-adaptive fused dispatch: ``sched_many`` with per-window chunk
    sizes chosen by a :class:`~repro.core.simulator.BurstDetector`.

    Walks the stream in ``segment``-event windows.  Before each window, one
    density sample is folded into ``detector`` (``densities[i]`` when given
    — e.g. ``Simulator.heap_density`` readings taken ahead of the clock —
    else the window's own event count, a pure stream-rate proxy) and the
    detector's answer picks the dispatch path: ``chunk == 1`` steps the
    window through the ``lax.scan`` path (sparse streams never pay
    kernel-launch padding for mostly-empty chunks), anything larger fuses
    the window through :func:`sched_many_fused` with that chunk.

    The detector is a pure observer — event order is untouched — so the
    result is **bitwise equal** to ``sched_many(state, events)`` for every
    detector state and density sequence (pinned in tests/test_scheduler.py).
    With a PRNG ``key`` (randomized tie-breaks live in the scan path) the
    whole stream takes the scan path unchanged.
    """
    if key is not None:
        return sched_many(state, events, key)
    if segment < 1:
        raise ValueError(f"segment must be >= 1, got {segment}")
    n = events.shape[0]
    n_windows = -(-n // segment)
    if densities is not None and len(densities) < n_windows:
        raise ValueError(
            f"densities has {len(densities)} samples for {n_windows} windows"
        )
    ws, warms = [], []
    for i in range(n_windows):
        ev = events[i * segment : (i + 1) * segment]
        sample = float(densities[i]) if densities is not None else float(ev.shape[0])
        chunk = detector.observe(sample)
        if chunk <= 1:
            state, (a, warm) = sched_many(state, ev, None)
        else:
            state, (a, warm) = sched_many_fused(
                state, ev, chunk=chunk, interpret=interpret
            )
        ws.append(a)
        warms.append(warm)
    ws_all = jnp.concatenate(ws) if ws else jnp.zeros((0,), jnp.int32)
    warm_all = (
        jnp.concatenate(warms).astype(bool) if warms else jnp.zeros((0,), bool)
    )
    return state, (ws_all, warm_all)


# ---------------------------------------------------------------- invariants
def check_invariants(state: JIQState) -> bool:
    """Structural invariants used by property tests."""
    ok = bool(jnp.all(state.idle >= 0)) and bool(jnp.all(state.conns >= 0))
    return ok
