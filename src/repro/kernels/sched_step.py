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
:func:`launch`, padding masked with +INF connections); the whole idle table and the
``(1, W)`` connection row are resident in VMEM; the event loop is a
fori_loop that loads the event's function row with a dynamic *sublane*
index.  Every update is a whole-row ``where(lane == w, ...)`` written back as
a row: the TPU compiler rejects single-lane dynamic slices on the lane axis,
and its argmin handles float32 only, so the lowest-index argmin is a row min
followed by the min lane index attaining it.

``sched_events_hbm`` is the same kernel for a table that VMEM cannot hold
(:func:`vmem_bytes` past :data:`VMEM_CAP_BYTES`): the table stays in HBM
and only the connection row is resident.  It works on the table's
worker-major view ``idle.T``, ``(W, F)``, functions on the lanes: the TPU
lays an ``(F, W)`` int32 array out by default with the side that pads less
to 128 on the lanes, which is F wherever W pads more (8,192 x 5,000 among
them), so there the view costs no copy, and the table crosses the
program's boundary in its default layout (JAX's persistent compile cache
does not keep a non-default one).
The kernel copies the input table to its output with one HBM-to-HBM DMA.
An ARRIVAL then needs its function's row, which lies in the ``(W, 128)``
column block of the ``128`` functions around it.  A block comes into VMEM
once, transposed to rows, into one of the launch's resident slots
(:func:`hbm_slots` of them, the first distinct blocks that ARRIVALs bring
in; nothing is ever evicted), or, once those are taken, into a spill slot
that the next such ARRIVAL overwrites.  An event on a resident block, an
ARRIVAL or a FINISH or EVICT after the block's first ARRIVAL, reads and
updates its row in the slot and loads nothing.  Every event writes
through the ``(8, 128)`` tile that holds the one count it may change (built
from the slot, or for a FINISH or EVICT on a block with no slot, brought in
on its own), so HBM is exact after each event and nothing is written back
at the end; a no-op loads nothing.  The TPU compiler slices an HBM table
only in whole tiles, so a block spans the rows up to the next multiple of
8 (all rows of a table of fewer than 8 workers) and 128 lanes; rows past W
are tile padding, masked with +INF connections and written back as they
came.

The ``pallas_call`` is named ``_sched_events`` (``_sched_events_hbm`` for
the HBM form): the chip benchmark's kernel matcher finds the custom call by
that prefix in the device trace, whatever the jit wrapper around it is
called.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_INF = 2**30  # python int: jnp scalars would be captured as kernel constants
_TILE_ROWS = 8  # sublanes of an int32 (8, 128) tile


def _lanes(n_workers: int) -> int:
    return -(-n_workers // 128) * 128


def vmem_bytes(n_funcs: int, n_workers: int) -> int:
    """Scoped VMEM the kernel asks for at ``(F, W)``: the idle table
    (``(8, 128)`` int32 tiles) and the connection row, each in and out, plus
    24 ``(1, W)`` rows for the event loop's temporaries (the v5e compiler
    takes up to ~19 when the limit allows) and 1 MiB."""
    lanes = _lanes(n_workers)
    sublanes = -(-n_funcs // 8) * 8
    return 4 * lanes * (2 * (sublanes + 1) + 24) + 2**20


def hbm_vmem_bytes(n_workers: int, slots: int = 1) -> int:
    """Scoped VMEM :func:`sched_events_hbm` asks for at ``W`` workers with
    ``slots`` resident column blocks (:func:`hbm_slots`), whatever the
    number of functions: the ``(W, 128)`` staging block, ``slots + 1``
    transposed ``(128, W)`` blocks (the last is the spill slot), the
    connection row in, out and resident, the same 24 rows of temporaries as
    :func:`vmem_bytes`, one ``(8, 128)`` tile, and 1 MiB (compiled for a
    v5e, the kernel's temporaries take 13-20 rows at 5,000-70,664 workers).
    At the default one slot this is the least the kernel runs with, which
    :func:`table_form` holds to the cap."""
    return 4 * _lanes(n_workers) * ((slots + 2) * 128 + 3 + 24) + 4 * _TILE_ROWS * 128 + 2**20


def hbm_slots(n_funcs: int, n_workers: int) -> int:
    """The column blocks :func:`sched_events_hbm` keeps resident through a
    launch at ``(F, W)``: as many as keep :func:`hbm_vmem_bytes` within half
    of :data:`VMEM_CAP_BYTES`, at most the table's ``ceil(F / 128)`` blocks
    and at least 1.  Half, because a skewed mix puts most events on a few
    blocks: at 8,192 x 5,000 this gives 19 slots (~54 MiB), which serve
    86.8% of the events of the chip benchmark's ``k8s5000w-batch256``
    traffic (one seed's 300 calls replayed on the host) against 86.9% with
    all 64 blocks resident, so the rest of the cap would buy next to
    nothing."""
    slot = 4 * 128 * _lanes(n_workers)
    fit = (VMEM_CAP_BYTES // 2 - hbm_vmem_bytes(n_workers, 0)) // slot
    return int(max(1, min(-(-n_funcs // 128), fit)))


def hbm_row_loads(kinds, funcs, slots: int, chunk: int) -> tuple[int, int]:
    """What :func:`sched_events_hbm` loads from HBM for host arrays of event
    kinds and functions run in launches of ``chunk`` events with ``slots``
    resident blocks: ``(row_loads, resident)``.  In each launch the first
    ``slots`` distinct column blocks (``func // 128``) that ARRIVALs bring
    in stay resident.  An ARRIVAL, FINISH or EVICT (kinds 0-2) on such a
    block after its first ARRIVAL is served from VMEM (``resident``); any
    other one makes a row load (an ARRIVAL its block, a FINISH or EVICT the
    tile holding its count); any other kind, a no-op, makes neither.
    Vectorised, with no loop over events: the call's span counts it."""
    kinds = np.asarray(kinds)
    event = np.arange(len(kinds))
    real = (kinds >= 0) & (kinds <= 2)
    block = np.where(real, funcs, 0) // 128
    n_blocks = int(block.max(initial=0)) + 1
    key = event // chunk * n_blocks + block  # (launch, block)
    arrivals = event[kinds == 0]
    first = np.full((-(-len(kinds) // chunk), n_blocks), len(kinds))
    np.minimum.at(first.reshape(-1), key[arrivals], arrivals)  # each block's first ARRIVAL
    if slots < n_blocks:  # the first ``slots`` blocks in each launch stay
        last = np.partition(first, slots - 1, axis=1)[:, slots - 1:slots]
        first[first > last] = len(kinds)
    resident = real & (event > first.reshape(-1)[key])
    n = int(np.count_nonzero(resident))
    return int(np.count_nonzero(real)) - n, n


def _first_argmin(scores, lane):
    """Lowest lane index attaining the row minimum (``jnp.argmin``'s tie)."""
    m = jnp.min(scores)
    return jnp.min(jnp.where(scores == m, lane, _INF))


def _event(k, w_ev, lane, load_row, load_conns):
    """One event on its function's idle row and the connection row, both
    ``(1, lanes)``.  The two callables load them, after the kind flags, as
    the VMEM kernel always has.  Returns ``(row', conns', is_arr, w_assign,
    warm)``; any kind but 0-2 changes nothing."""
    is_arr = (k == 0).astype(jnp.int32)
    is_fin = (k == 1).astype(jnp.int32)
    is_evt = (k == 2).astype(jnp.int32)

    row = load_row()
    conns = load_conns()
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
    at_ev = lane == w_ev  # ARRIVAL carries -1: matches no lane
    row = row + jnp.where(at_ev, is_fin, 0)
    row = row - jnp.where(at_ev & (row > 0), is_evt, 0)
    conns = jnp.where(at_ev, jnp.maximum(conns - is_fin, 0), conns)
    return row, conns, is_arr, w_assign, dec


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
        row, conns, is_arr, w_assign, dec = _event(
            k, workers_ref[i], lane,
            lambda: idle_out[pl.ds(f, 1), :], lambda: conns_out[...],
        )
        idle_out[pl.ds(f, 1), :] = row
        conns_out[...] = conns
        assign_ref[i] = jnp.where(is_arr == 1, w_assign, -1)
        warm_ref[i] = dec
        return 0

    jax.lax.fori_loop(0, R, body, 0)


def _column_rows(n_workers: int) -> int:
    """Rows of the worker-major table that a DMA of a column block spans:
    W up to whole 8-row tiles, or all W rows of a table of fewer."""
    return n_workers if n_workers < _TILE_ROWS else -(-n_workers // _TILE_ROWS) * _TILE_ROWS


def _sched_events_hbm_kernel(
    n_workers, copy, kinds_ref, funcs_ref, workers_ref, table_ref, conns_ref,
    assign_ref, warm_ref, table_out, conns_out, cols, rows, tile, conns,
    slot_of, used, sem,
):
    if copy:
        whole = pltpu.make_async_copy(table_ref, table_out, sem)
        whole.start()
    n_rows = _column_rows(n_workers)
    n_tile = tile.shape[0]
    lanes = conns.shape[1]
    spill = rows.shape[0] - 1  # the slot past the resident ones
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    real = lane < n_workers  # rows past W are tile padding
    sub8 = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    fn8 = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    fn = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    conns[...] = jnp.full((1, lanes), _INF, jnp.int32)
    conns[:, pl.ds(0, n_workers)] = conns_ref[...]
    used[0] = 0

    def no_slot(b, _):
        slot_of[b] = -1
        return 0

    jax.lax.fori_loop(0, slot_of.shape[0], no_slot, 0)
    if copy:
        whole.wait()

    def dma(src, dst):
        c = pltpu.make_async_copy(src, dst, sem)
        c.start()
        c.wait()

    def tile_of(w, fb):
        """First row of the tile holding worker ``w``'s counts of functions
        ``fb..fb+127``, and that tile in HBM."""
        if n_tile == n_rows:  # the tile is every row
            base = 0
        else:
            base = pl.multiple_of((w // n_tile) * n_tile, n_tile)
        return base, table_out.at[pl.ds(base, n_tile), pl.ds(fb, 128)]

    def update_through(s, r, w, fb, update):
        """Apply ``update(counts, at)`` to the ``(8, 128)`` window of slot
        ``s`` that holds row ``r``'s count of worker ``w`` (``at`` marks that
        count; a worker past W matches nothing), then write the tile of HBM
        that holds it from the slot: the 128 workers around it, transposed
        back through the staging block.  An event changes one count, so no
        whole row is rewritten."""
        w_in = jnp.clip(w, 0, n_workers - 1)
        rb = pl.multiple_of((r // 8) * 8, 8)
        lb = pl.multiple_of((w_in // 128) * 128, 128)
        window = rows.at[s, pl.ds(rb, 8), pl.ds(lb, 128)]
        window[...] = update(window[...], (sub8 + rb == r) & (fn8 + lb == w))
        base, hbm = tile_of(w_in, fb)
        cols[pl.ds(0, 128), :] = rows[s, :, pl.ds(lb, 128)].T
        dma(cols.at[pl.ds(base - lb, n_tile), :], hbm)

    def body(i, _):
        k = kinds_ref[i]
        f = funcs_ref[i]
        w_ev = workers_ref[i]
        b = f // 128
        fb = pl.multiple_of(b * 128, 128)
        assign_ref[i] = -1
        warm_ref[i] = 0

        @pl.when(k == 0)
        def _arrival():
            @pl.when(slot_of[b] < 0)
            def _load():
                # the block into the next free slot while one is left, else
                # into the spill slot
                u = used[0]
                dma(table_out.at[pl.ds(0, n_rows), pl.ds(fb, 128)], cols.at[pl.ds(0, n_rows), :])
                rows[jnp.minimum(u, spill)] = cols[...].T

                @pl.when(u < spill)
                def _keep():
                    slot_of[b] = u
                    used[0] = u + 1

            s = slot_of[b]
            s = jnp.where(s < 0, spill, s)
            _, c, _, w_assign, dec = _event(
                k, w_ev, lane, lambda: jnp.where(real, rows[s, pl.ds(f - fb, 1), :], 0),
                lambda: conns[...],
            )
            conns[...] = c
            # the ARRIVAL changes at most the count at (w_assign, f)
            update_through(s, f - fb, w_assign, fb, lambda x, at: x - jnp.where(at, dec, 0))
            assign_ref[i] = w_assign
            warm_ref[i] = dec

        @pl.when((k == 1) | (k == 2))
        def _notice():
            # FINISH: pull enqueue + close connection; EVICT: notification
            # removal (the VMEM kernel's rules, on the one count they touch)
            is_fin = (k == 1).astype(jnp.int32)
            is_evt = (k == 2).astype(jnp.int32)
            s = slot_of[b]

            def notice(x, at):
                x = x + jnp.where(at, is_fin, 0)
                return x - jnp.where(at & (x > 0), is_evt, 0)

            @pl.when(s >= 0)
            def _resident():
                update_through(s, f - fb, w_ev, fb, notice)

            @pl.when(s < 0)
            def _load():
                base, hbm = tile_of(jnp.clip(w_ev, 0, n_workers - 1), fb)
                dma(hbm, tile)
                at = (sub + base == w_ev) & (fn == f - fb)  # a worker past W matches nothing
                tile[...] = notice(tile[...], at)
                dma(tile, hbm)

            c = conns[...]
            conns[...] = jnp.where(lane == w_ev, jnp.maximum(c - is_fin, 0), c)

        return 0

    jax.lax.fori_loop(0, kinds_ref.shape[0], body, 0)
    conns_out[...] = conns[:, pl.ds(0, n_workers)]


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
        name="_sched_events",
    )(kinds, funcs, workers, idle, conns)


def _as_in_hbm(table):
    """The worker-major ``table`` ``(W, F)`` as the chip's HBM holds it, for
    the interpreter: rows padded to whole 8-row tiles (a table of fewer
    keeps them) and lanes to a multiple of 128, the padding holding idle
    counts no worker or function has, which the kernel must mask and leave
    as they are."""
    W, F = table.shape
    return jnp.pad(table, ((0, _column_rows(W) - W), (0, -(-F // 128) * 128 - F)),
                   constant_values=1)


def sched_events_hbm(
    kinds: jax.Array,    # (R,) int32 — 0 ARRIVAL / 1 FINISH / 2 EVICT
    funcs: jax.Array,    # (R,) int32
    workers: jax.Array,  # (R,) int32 (-1 for ARRIVAL)
    idle: jax.Array,     # (F, W) int32, in HBM
    conns: jax.Array,    # (1, W) int32
    in_place: bool = False,
    interpret: bool = False,
    slots: int | None = None,
):
    """:func:`sched_events` with the idle table left in HBM: the same
    arguments, results and semantics, for any ``(F, W)`` whose
    :func:`hbm_vmem_bytes` fits VMEM.  W is taken as it is.

    The kernel works on ``idle.T`` (see the module's notes: no copy where
    the TPU lays ``idle`` out worker-major, as it does where W pads more to
    128 than F; XLA transposes around the kernel for other shapes) and
    writes its whole output table: one HBM-to-HBM DMA of the input, then
    the tiles the events change.  ``in_place=True`` makes the output alias
    the input and copies nothing, for a table the caller may overwrite (one
    its own program made).  ``slots`` column blocks stay resident through
    the launch (default :func:`hbm_slots` of the shape; tests set fewer);
    :func:`hbm_row_loads` counts what the events load from HBM and what
    they find resident.  ``interpret=True`` runs it in the TPU interpreter
    (its DMAs included) on the table padded as HBM holds it
    (:func:`_as_in_hbm`).
    """
    R = kinds.shape[0]
    F, W = idle.shape
    if slots is None:
        slots = hbm_slots(F, W)
    table = idle.T
    if interpret:
        table = _as_in_hbm(table)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    row = pl.BlockSpec((1, W), lambda: (0, 0))
    a, warm, table2, conns2 = pl.pallas_call(
        functools.partial(_sched_events_hbm_kernel, W, not in_place),
        in_specs=[smem, smem, smem, hbm, row],
        out_specs=[smem, smem, hbm, row],
        out_shape=[
            jax.ShapeDtypeStruct((R,), jnp.int32),
            jax.ShapeDtypeStruct((R,), jnp.int32),
            jax.ShapeDtypeStruct(table.shape, jnp.int32),
            jax.ShapeDtypeStruct((1, W), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((_lanes(W), 128), jnp.int32),
            pltpu.VMEM((slots + 1, 128, _lanes(W)), jnp.int32),
            pltpu.VMEM((min(W, _TILE_ROWS), 128), jnp.int32),
            pltpu.VMEM((1, _lanes(W)), jnp.int32),
            pltpu.SMEM((-(-F // 128),), jnp.int32),  # a block's slot, or -1
            pltpu.SMEM((1,), jnp.int32),  # slots taken
            pltpu.SemaphoreType.DMA(()),
        ],
        input_output_aliases={3: 2} if in_place else {},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=hbm_vmem_bytes(W, slots)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="_sched_events_hbm",
    )(kinds, funcs, workers, table, conns)
    if interpret:
        table2 = table2[:W, :F]
    return a, warm, table2.T, conns2


# ------------------------------------------------------------ the wrapper
# The choice between the two forms (the cap, the byte counts and the
# backend check) and the one launch of either; nothing here imports the
# rest of the package.

# v5e holds 128 MiB of VMEM per core (pltpu.get_tpu_info); an eighth stays
# free for Mosaic's internal scratch.
VMEM_CAP_BYTES = 112 * 2**20


def require_tpu(what: str) -> None:
    """Raise unless JAX's default backend is the TPU: the native kernels
    have no CPU/GPU lowering and nothing substitutes another path."""
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"{what} runs the native Pallas TPU kernel, but JAX's default "
            f"backend is {backend!r}; pass interpret=True to run the kernel "
            "body in Pallas's interpreter"
        )


def table_form(what: str, idle_shape, interpret: bool) -> str:
    """Where the kernel keeps an ``(F, W)`` idle table, chosen from its
    shape: ``"vmem"`` (:func:`sched_events`) wherever :func:`vmem_bytes`
    fits :data:`VMEM_CAP_BYTES`, else ``"hbm"`` (:func:`sched_events_hbm`).
    Natively this raises unless the backend is the TPU (:func:`require_tpu`)
    and the HBM form's VMEM need (:func:`hbm_vmem_bytes`, O(W)) fits the
    cap; interpret mode needs neither."""
    F, W = idle_shape
    table = "vmem" if vmem_bytes(F, W) <= VMEM_CAP_BYTES else "hbm"
    if interpret:
        return table
    require_tpu(what)
    need = hbm_vmem_bytes(W)
    if table == "hbm" and need > VMEM_CAP_BYTES:
        raise ValueError(
            f"{what}: {F} functions x {W} workers needs {need} bytes of VMEM "
            f"even with the table in HBM, over the "
            f"{VMEM_CAP_BYTES}-byte cap; split the workers into shards "
            "of fewer lanes"
        )
    return table


@functools.partial(jax.jit, static_argnames=("interpret", "table", "in_place"))
def launch(kinds, funcs, workers, idle, conns, interpret, table, in_place=False):
    """One launch of the kernel in the form ``table`` names
    (:func:`table_form`), on ``conns`` of shape ``(W,)``.  Returns
    ``(assign, warm, idle', conns')``.  The VMEM form pads W to whole
    128-lane rows, the padding masked with +INF connections, and cuts it
    back; the HBM form takes W as it is, and with ``in_place`` updates
    ``idle`` where it lies (:func:`sched_events_hbm`)."""
    if table == "hbm":
        a, warm, idle2, conns2 = sched_events_hbm(
            kinds, funcs, workers, idle, conns[None, :], in_place=in_place,
            interpret=interpret,
        )
        return a, warm, idle2, conns2[0]
    W = idle.shape[1]
    padW = _lanes(W) - W
    if padW:
        idle = jnp.pad(idle, ((0, 0), (0, padW)))
        conns = jnp.pad(conns, (0, padW), constant_values=_INF)  # never selected
    a, warm, idle2, conns2 = sched_events(
        kinds, funcs, workers, idle, conns[None, :], interpret=interpret
    )
    conns2 = conns2[0]
    if padW:
        idle2, conns2 = idle2[:, :W], conns2[:W]
    return a, warm, idle2, conns2
