"""Jit'd public wrappers around the Pallas kernels.

Kernels compile natively for the TPU.  ``interpret=True`` runs the kernel
bodies in Pallas's interpreter instead, which is how the CPU tests run them;
nothing chooses interpret mode on the caller's behalf.  Wrappers handle
padding/layout so call sites stay shape-clean.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import decode_attention as _dec
from . import flash_attention as _fa
from . import sched_step as _ss
from . import ssd_scan as _ssd
from . import ref


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, causal: bool = True, window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128, interpret: bool = False):
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "block_k", "interpret"))
def decode_attention(q, k_cache, v_cache, valid_len, window: Optional[int] = None,
                     block_k: int = 512, interpret: bool = False):
    return _dec.decode_attention(q, k_cache, v_cache, valid_len, window=window,
                                 block_k=block_k, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "block_h", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, chunk: int = 128, block_h: int = 8,
             interpret: bool = False):
    if Bm.shape[2] != 1:  # kernel covers ngroups=1; general case -> oracle
        return ref.ssd_scan_ref(x, dt, A, Bm, Cm, chunk)
    S = x.shape[1]
    pad = (-S) % chunk
    if pad:
        x, dt, Bm, Cm = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                         for t in (x, dt, Bm, Cm))
    y, st = _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, block_h=block_h, interpret=interpret)
    return (y[:, :S] if pad else y), st


# v5e holds 128 MiB of VMEM per core (pltpu.get_tpu_info); an eighth stays
# free for Mosaic's internal scratch.
SCHED_VMEM_CAP_BYTES = 112 * 2**20


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


def check_sched_events(what: str, idle_shape, interpret: bool) -> None:
    """Raise unless the native kernel can run an ``(F, W)`` idle table here:
    the TPU (:func:`require_tpu`) and a table that fits
    :data:`SCHED_VMEM_CAP_BYTES`.  Interpret mode needs neither."""
    if interpret:
        return
    require_tpu(what)
    F, W = idle_shape
    need = _ss.vmem_bytes(F, W)
    if need > SCHED_VMEM_CAP_BYTES:
        raise ValueError(
            f"{what}: {F} functions x {W} workers needs "
            f"{need} bytes of VMEM, over the {SCHED_VMEM_CAP_BYTES}-byte "
            "cap; split the workers into shards of fewer lanes"
        )


def sched_events(kinds, funcs, workers, idle, conns, interpret: bool = False):
    """Fused mixed (ARRIVAL|FINISH|EVICT) burst: pad lanes, run, unpad.

    Returns ``(assign (R,), warm (R,) int32, idle' (F, W), conns' (W,))``.
    Natively this needs the TPU and a table that fits the VMEM cap
    (:func:`check_sched_events`); a wider cluster raises rather than
    running elsewhere.
    """
    check_sched_events("ops.sched_events", idle.shape, interpret)
    return _sched_events(kinds, funcs, workers, idle, conns, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _sched_events(kinds, funcs, workers, idle, conns, interpret):
    F, W = idle.shape
    padW = (-W) % 128 if not interpret else 0
    if padW:
        idle = jnp.pad(idle, ((0, 0), (0, padW)))
        conns = jnp.pad(conns, (0, padW), constant_values=2**30)  # never selected
    a, warm, idle2, conns2 = _ss.sched_events(
        kinds, funcs, workers, idle, conns[None, :], interpret=interpret
    )
    conns2 = conns2[0]
    if padW:
        idle2, conns2 = idle2[:, :W], conns2[:W]
    return a, warm, idle2, conns2
