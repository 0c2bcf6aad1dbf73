"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests).

Each function mirrors the exact contract of its kernel in ops.py:
  flash_attention_ref  <-> kernels/flash_attention.py
  decode_attention_ref <-> kernels/decode_attention.py
  ssd_scan_ref         <-> kernels/ssd_scan.py  (the chunked SSD of
                           models/mamba.py, re-exported for the sweep tests)
  sched_step_ref       <-> kernels/sched_step.py on ARRIVAL-only bursts
                           (vectorized Algorithm 1 over a request burst)
Mixed event bursts are checked against ``core.jax_sched.sched_many``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from ..models.mamba import ssd_chunked as _ssd_chunked

_NEG_INF = -2.0e38


def flash_attention_ref(
    q: jax.Array,  # (B, S, H, hd)
    k: jax.Array,  # (B, S, KH, hd)
    v: jax.Array,  # (B, S, KH, hd)
    causal: bool = True,
    window: int | None = None,
) -> jax.Array:
    B, S, H, hd = q.shape
    KH = k.shape[2]
    G = H // KH
    qg = q.reshape(B, S, KH, G, hd)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qg, k, preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(S)
    ok = jnp.ones((S, S), bool)
    if causal:
        ok &= pos[None, :] <= pos[:, None]
    if window is not None:
        ok &= (pos[:, None] - pos[None, :]) < window
    logits = jnp.where(ok[None, None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v)
    return out.reshape(B, S, H, hd)


def decode_attention_ref(
    q: jax.Array,        # (B, H, hd) — one new token per sequence
    k_cache: jax.Array,  # (B, S, KH, hd)
    v_cache: jax.Array,  # (B, S, KH, hd)
    valid_len: jax.Array,  # scalar int32: entries [0, valid_len] are live
    window: int | None = None,
) -> jax.Array:
    B, S, KH, hd = k_cache.shape
    H = q.shape[1]
    G = H // KH
    qg = q.reshape(B, KH, G, hd)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    logits = jnp.einsum(
        "bkgh,bskh->bkgs", qg, k_cache.astype(q.dtype), preferred_element_type=jnp.float32
    ) * scale
    pos = jnp.arange(S)
    ok = pos <= valid_len
    if window is not None:
        ok &= (valid_len - pos) < window
    logits = jnp.where(ok[None, None, None, :], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgs,bskh->bkgh", probs, v_cache.astype(q.dtype))
    return out.reshape(B, H, hd)


def ssd_scan_ref(x, dt, A, Bm, Cm, chunk: int, init_state=None):
    """Chunked SSD oracle — delegates to the model's pure-jnp implementation."""
    return _ssd_chunked(x, dt, A, Bm, Cm, chunk, init_state)


def sched_step_ref(
    funcs: jax.Array,  # (R,) int32 — function id per request, in order
    idle: jax.Array,   # (F, W) int32 — PQ_f multiset (idle instances)
    conns: jax.Array,  # (W,) int32 — active connections
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Vectorized Algorithm-1 ARRIVAL burst (deterministic first-index ties).

    Returns (assignments (R,), warm (R,), idle', conns').
    """
    INF = jnp.int32(2**30)

    def step(carry, f):
        idle, conns = carry
        row = idle[f]
        has_idle = jnp.any(row > 0)
        pull_scores = jnp.where(row > 0, conns, INF)
        w = jnp.where(has_idle, jnp.argmin(pull_scores), jnp.argmin(conns)).astype(jnp.int32)
        idle = idle.at[f, w].add(-has_idle.astype(jnp.int32))
        conns = conns.at[w].add(1)
        return (idle, conns), (w, has_idle)

    (idle, conns), (ws, warm) = jax.lax.scan(step, (idle, conns), funcs)
    return ws, warm, idle, conns

