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
(``sched_many_fused``); ``sched_many`` is the scan it is checked against.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..kernels import sched_step as _ss
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


# The longest kernel launch: a burst of more events splits into launches of
# this many and one of the rest.
CHUNK = 1024


def sched_many_fused(
    state: JIQState, events: jax.Array, interpret: bool = False
) -> Tuple[JIQState, Tuple[jax.Array, jax.Array]]:
    """``sched_many`` with the whole stream fused into Pallas kernel launches.

    Each :data:`CHUNK` of mixed (ARRIVAL|FINISH|EVICT) events costs *one*
    kernel launch (``kernels/sched_step.launch``) instead of one scan
    iteration per event, and a ragged tail one launch of exactly its length
    (:func:`_split`): no launch runs an event it was not given.  State is
    carried between launches.  The whole call is one jitted program
    (:func:`_fused_call`), compiled once per stream length.  Bit-exact
    against ``sched_many(state, events, key=None)``; randomized tie-breaks
    are ``sched_many``'s alone.

    The table's shape picks the kernel's form
    (``kernels/sched_step.table_form``): the whole table in VMEM where it
    fits, else the table in HBM with the column blocks that a launch's
    first ARRIVALs bring in kept in VMEM, and the part of its function's
    row that any other event needs brought in per event
    (``sched_step.sched_events_hbm``).
    The table goes in and comes out in its default layout on either form.
    The kernel runs natively, which needs the TPU: on any other backend
    this raises unless ``interpret=True`` runs the kernel body in Pallas's
    interpreter.

    The call writes profiler spans (``jax.profiler.TraceAnnotation``;
    they cost about a microsecond each when no trace is active):
    ``sched.fused`` around the whole call, with the metadata ``chunks``
    (kernel launches), ``events`` (real events handed in), ``padded``
    (events the launches run past those handed in, from the same
    :func:`_split` as the program: 0, as no launch is padded),
    ``compiles`` (backend compiles during the call,
    :func:`repro.utils.compile_cache.compile_count`), ``table`` (the form,
    ``"vmem"`` or ``"hbm"``) and, where ``events`` is a host (NumPy) array,
    ``row_loads`` (loads of function rows the kernel makes from HBM,
    counted from the events by the kernel's rule,
    ``sched_step.hbm_row_loads``; 0 on the VMEM form; left out for a device
    array, which it would have to fetch) and, beside it on the HBM form,
    ``resident`` (the events the kernel serves from a column block it
    keeps in VMEM, by the same rule); and inside it, once each,
    ``sched.stage_in`` (the host's checks of the backend and the table's
    VMEM need), ``sched.launch`` (the dispatch of the one program, which
    also moves host events to the device: on the TPU host that costs less
    than a ``jax.device_put`` of them first) and ``sched.stage_out`` (the
    result built from its outputs).
    """
    chunk = CHUNK
    count = compile_count()
    compiles = count.compiles
    with TraceAnnotation("sched.fused") as call:
        with TraceAnnotation("sched.stage_in"):
            table = _ss.table_form("sched_many_fused", state.idle.shape, interpret)
        with TraceAnnotation("sched.launch"):
            idle, conns, assign, warm = _fused_call(
                events, state.idle, state.conns, chunk=chunk, interpret=interpret, table=table
            )
        with TraceAnnotation("sched.stage_out"):
            out = JIQState(idle, conns), (assign, warm)
        n = events.shape[0]
        full, tail = _split(n, chunk)
        meta = dict(chunks=full + (tail > 0), events=n, padded=full * chunk + tail - n,
                    compiles=count.compiles - compiles, table=table)
        if isinstance(events, np.ndarray):
            if table == "vmem":
                meta["row_loads"] = 0
            else:
                slots = _ss.hbm_slots(*state.idle.shape)
                meta["row_loads"], meta["resident"] = _ss.hbm_row_loads(
                    events[:, 0], events[:, 1], slots, chunk)
        call.set_metadata(**meta)
    return out


def _split(n: int, chunk: int) -> Tuple[int, int]:
    """The kernel launches of an ``n``-event call: ``full`` launches of
    ``chunk`` events, then, where ``tail`` > 0, one of ``tail``."""
    return divmod(n, chunk)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "table"))
def _fused_call(events, idle, conns, chunk, interpret, table):
    """One ``sched_many_fused`` call: run the kernel over the ``(n, 3)``
    stream in the launches :func:`_split` gives, the whole chunks (a
    ``lax.scan`` past the first) and then the tail, with the state carried.
    Returns ``(idle', conns', assign, warm)``, the answers being the
    launches' own outputs in order.  The first launch of the HBM form
    copies the table and every later one updates that copy in place (the
    VMEM form ignores ``in_place``).  No events make no launch."""
    full, tail = _split(events.shape[0], chunk)

    def run(carry, e, in_place):
        a, warm, idle, conns = _ss.launch(
            e[:, 0], e[:, 1], e[:, 2], *carry, interpret=interpret, table=table,
            in_place=in_place,
        )
        return (idle, conns), (a, warm)

    carry, answers = (idle, conns), []
    whole = events[: full * chunk].reshape(full, chunk, 3)
    if full:
        carry, first = run(carry, whole[0], in_place=False)
        answers.append(first)
    if full > 1:
        carry, rest = jax.lax.scan(functools.partial(run, in_place=True), carry, whole[1:])
        answers.append(jax.tree.map(lambda x: x.reshape(-1), rest))
    if tail:
        carry, last = run(carry, events[full * chunk:], in_place=full > 0)
        answers.append(last)
    if not answers:
        return idle, conns, jnp.zeros((0,), jnp.int32), jnp.zeros((0,), bool)
    a, warm = (jnp.concatenate(x) for x in zip(*answers))
    return *carry, a, warm.astype(bool)


# ---------------------------------------------------------------- invariants
def check_invariants(state: JIQState) -> bool:
    """Structural invariants used by property tests."""
    ok = bool(jnp.all(state.idle >= 0)) and bool(jnp.all(state.conns >= 0))
    return ok
