"""Jit'd public wrappers around the attention and SSD Pallas kernels (the
dispatch kernel's own wrapper is ``sched_step.launch``).

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

