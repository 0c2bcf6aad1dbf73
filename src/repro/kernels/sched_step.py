"""Fused pull-based scheduling in Pallas: mixed ARRIVAL/FINISH/EVICT bursts.

The paper's own hot path: for each request in a burst, (1) masked-argmin over
workers with an idle instance of the requested function (the PQ_f dequeue),
(2) least-connections argmin fallback, (3) connection/idle-table updates that
the *next* event in the burst observes.  The sequential dependence makes
this a scan — fused here into one kernel invocation so the whole burst costs
one dispatch (vs. one XLA scan iteration each; see benchmarks/bench_kernels).

``sched_events`` runs full mixed ``(ARRIVAL|FINISH|EVICT)`` event streams,
the fused form of ``core.jax_sched.sched_step`` scanned over a burst: FINISH
performs the pull enqueue (idle[f, w] += 1, connection closes), EVICT the
notification removal.  Bit-exact against ``sched_many(..., key=None)``
(deterministic lowest-index ties); exposed as
``core.jax_sched.sched_many_fused`` with chunking.  An ARRIVAL-only burst is
the vectorized Algorithm 1 of ``ref.sched_step_ref``.

Layout: workers live on the 128-lane axis (W padded to a lane multiple by
ops.py, padding masked with +INF connections); the whole idle table and the
``(1, W)`` connection row are resident in VMEM; the event loop is a
fori_loop that loads the event's function row with a dynamic *sublane*
index.  Every update is a whole-row ``where(lane == w, ...)`` written back as
a row: the TPU compiler rejects single-lane dynamic slices on the lane axis,
and its argmin handles float32 only, so the lowest-index argmin is a row min
followed by the min lane index attaining it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INF = 2**30  # python int: jnp scalars would be captured as kernel constants


def vmem_bytes(n_funcs: int, n_workers: int) -> int:
    """Scoped VMEM the kernel asks for at ``(F, W)``: the idle table
    (``(8, 128)`` int32 tiles) and the connection row, each in and out, plus
    24 ``(1, W)`` rows for the event loop's temporaries (the v5e compiler
    takes up to ~19 when the limit allows) and 1 MiB."""
    lanes = -(-n_workers // 128) * 128
    sublanes = -(-n_funcs // 8) * 8
    return 4 * lanes * (2 * (sublanes + 1) + 24) + 2**20


def _first_argmin(scores, lane):
    """Lowest lane index attaining the row minimum (``jnp.argmin``'s tie)."""
    m = jnp.min(scores)
    return jnp.min(jnp.where(scores == m, lane, _INF))


def _sched_events_kernel(
    kinds_ref, funcs_ref, workers_ref, idle_ref, conns_ref,
    assign_ref, warm_ref, idle_out, conns_out,
):
    idle_out[...] = idle_ref[...]
    conns_out[...] = conns_ref[...]
    R = kinds_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, conns_ref.shape, 1)  # (1, W)

    def body(i, _):
        k = kinds_ref[i]
        f = funcs_ref[i]
        w_ev = workers_ref[i]  # ARRIVAL carries -1: matches no lane
        is_arr = (k == 0).astype(jnp.int32)
        is_fin = (k == 1).astype(jnp.int32)
        is_evt = (k == 2).astype(jnp.int32)

        row = idle_out[pl.ds(f, 1), :]  # (1, W)
        conns = conns_out[...]
        has_idle = jnp.max(row) > 0
        w_pull = _first_argmin(jnp.where(row > 0, conns, _INF), lane)
        w_fb = _first_argmin(conns, lane)
        w_assign = jnp.where(has_idle, w_pull, w_fb)

        # ARRIVAL: dequeue from PQ_f (if pulled) + open connection
        dec = is_arr * has_idle.astype(jnp.int32)
        at_assign = lane == w_assign
        row = row - jnp.where(at_assign, dec, 0)
        conns = conns + jnp.where(at_assign, is_arr, 0)

        # FINISH: pull enqueue + close connection; EVICT: notification removal
        at_ev = lane == w_ev
        row = row + jnp.where(at_ev, is_fin, 0)
        row = row - jnp.where(at_ev & (row > 0), is_evt, 0)
        conns = jnp.where(at_ev, jnp.maximum(conns - is_fin, 0), conns)

        idle_out[pl.ds(f, 1), :] = row
        conns_out[...] = conns
        assign_ref[i] = jnp.where(is_arr == 1, w_assign, -1)
        warm_ref[i] = dec
        return 0

    jax.lax.fori_loop(0, R, body, 0)


def sched_events(
    kinds: jax.Array,    # (R,) int32 — 0 ARRIVAL / 1 FINISH / 2 EVICT
    funcs: jax.Array,    # (R,) int32
    workers: jax.Array,  # (R,) int32 (-1 for ARRIVAL)
    idle: jax.Array,     # (F, W) int32
    conns: jax.Array,    # (1, W) int32
    interpret: bool = False,
):
    """Fused mixed-event burst.  Returns (assign, warm, idle', conns').

    One dispatch per burst; semantics identical to scanning
    ``core.jax_sched.sched_step`` with ``key=None`` (assign/warm are -1/0 for
    non-ARRIVAL events; any other kind is a no-op).  Scoped VMEM is sized
    by :func:`vmem_bytes`.
    """
    R = kinds.shape[0]
    F, W = idle.shape
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    table = pl.BlockSpec((F, W), lambda: (0, 0))
    row = pl.BlockSpec((1, W), lambda: (0, 0))
    return pl.pallas_call(
        _sched_events_kernel,
        in_specs=[smem, smem, smem, table, row],
        out_specs=[smem, smem, table, row],
        out_shape=[
            jax.ShapeDtypeStruct((R,), jnp.int32),
            jax.ShapeDtypeStruct((R,), jnp.int32),
            jax.ShapeDtypeStruct((F, W), jnp.int32),
            jax.ShapeDtypeStruct((1, W), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_bytes(F, W)),
        interpret=interpret,
    )(kinds, funcs, workers, idle, conns)
