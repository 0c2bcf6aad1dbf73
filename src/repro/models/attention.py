"""Attention variants: full/GQA, sliding-window, MLA (DeepSeek), cross-attn.

Forward paths:
* ``attn_forward``  — train/prefill over (B, S, d); returns output + KV for the
  cache.  Sliding-window / global masks are driven by *traced per-layer
  scalars* so heterogeneous stacks (gemma3 5:1 local:global) stay scannable.
* ``attn_decode``   — one-token step against a fixed-size KV cache
  (flash-decode semantics; the Pallas kernel in kernels/decode_attention.py
  implements the same contraction).
* ``mla_*``         — MLA with the *absorbed* decode path: the cache holds the
  compressed latent (kv_lora + rope dims) and queries are absorbed through
  W_UK / W_UV, so decode never materializes per-head K/V (DeepSeek-V2/V3).

The XLA (einsum) implementation is the reference and the dry-run path; Pallas
kernels are drop-in replacements on TPU via ``impl="pallas"`` (kernels/ops.py).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .layers import Param, apply_rope, param, rmsnorm

_NEG_INF = -2.0e38
# "window" value meaning full attention (numpy: no backend starts at import)
GLOBAL_WINDOW = np.int32(2**30)


# ------------------------------------------------------------------- params
def init_attention(key, cfg, dtype=jnp.float32) -> Dict[str, Param]:
    d, H, KH, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ks = jax.random.split(key, 8)
    p = {
        "wq": param(ks[0], (d, H, hd), ("embed", "heads", "head_dim"), dtype),
        "wk": param(ks[1], (d, KH, hd), ("embed", "kv_heads", "head_dim"), dtype),
        "wv": param(ks[2], (d, KH, hd), ("embed", "kv_heads", "head_dim"), dtype),
        "wo": param(ks[3], (H, hd, d), ("heads", "head_dim", "embed"), dtype),
    }
    if cfg.use_bias:
        p["bq"] = param(ks[4], (H, hd), ("heads", "head_dim"), dtype, init="zeros")
        p["bk"] = param(ks[5], (KH, hd), ("kv_heads", "head_dim"), dtype, init="zeros")
        p["bv"] = param(ks[6], (KH, hd), ("kv_heads", "head_dim"), dtype, init="zeros")
        p["bo"] = param(ks[7], (d,), ("embed",), dtype, init="zeros")
    if cfg.qk_norm:
        p["q_norm"] = param(ks[4], (hd,), ("head_dim",), init="zeros")
        p["k_norm"] = param(ks[5], (hd,), ("head_dim",), init="zeros")
    return p


def init_mla(key, cfg, dtype=jnp.float32) -> Dict[str, Param]:
    m, d, H = cfg.mla, cfg.d_model, cfg.n_heads
    qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 8)
    return {
        "wq_a": param(ks[0], (d, m.q_lora_rank), ("embed", "q_lora"), dtype),
        "q_norm": param(ks[1], (m.q_lora_rank,), ("q_lora",), init="zeros"),
        "wq_b": param(ks[2], (m.q_lora_rank, H, qk_hd), ("q_lora", "heads", "head_dim"), dtype),
        "wkv_a": param(ks[3], (d, m.kv_lora_rank + m.qk_rope_head_dim), ("embed", "kv_lora"), dtype),
        "kv_norm": param(ks[4], (m.kv_lora_rank,), ("kv_lora",), init="zeros"),
        "wkv_b": param(
            ks[5],
            (m.kv_lora_rank, H, m.qk_nope_head_dim + m.v_head_dim),
            ("kv_lora", "heads", "head_dim"),
            dtype,
        ),
        "wo": param(ks[6], (H, m.v_head_dim, d), ("heads", "head_dim", "embed"), dtype),
    }


# -------------------------------------------------------------------- core
def _mask_bias(q_pos: jax.Array, k_pos: jax.Array, window, causal: bool) -> jax.Array:
    """(Sq, Sk) additive mask. window is a traced int scalar (GLOBAL_WINDOW=full)."""
    dq = q_pos[:, None]
    dk = k_pos[None, :]
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        ok &= dk <= dq
    ok &= (dq - dk) < window  # sliding window (no-op when window is huge)
    return jnp.where(ok, 0.0, _NEG_INF)


def sdpa(
    q: jax.Array,  # (B, Sq, KH, G, hd)
    k: jax.Array,  # (B, Sk, KH, hd)
    v: jax.Array,  # (B, Sk, KH, hd)
    bias: Optional[jax.Array],  # broadcastable to (B, KH, G, Sq, Sk)
    softcap: Optional[float] = None,
) -> jax.Array:
    """Grouped-query attention without materializing repeated K/V."""
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    if bias is not None:
        logits = logits + bias
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bkgqs,bskh->bqkgh", probs, v)


# --------------------------------------------------------------- GQA paths
def attn_forward(
    p: Dict[str, jax.Array],
    x: jax.Array,  # (B, S, d)
    cfg,
    positions: jax.Array,  # (B, S)
    window=None,  # traced scalar or None -> full
    theta=None,
    causal: bool = True,
    kv_memory: Optional[Tuple[jax.Array, jax.Array]] = None,  # cross-attn K/V source
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    B, S, _ = x.shape
    H, KH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    G = H // KH
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    if kv_memory is None:
        k = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
        v = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
        if "bk" in p:
            k, v = k + p["bk"], v + p["bv"]
        k_pos = positions[0]
    else:
        mem, mem_pos = kv_memory
        k = jnp.einsum("btd,dhe->bthe", mem, p["wk"])
        v = jnp.einsum("btd,dhe->bthe", mem, p["wv"])
        if "bk" in p:
            k, v = k + p["bk"], v + p["bv"]
        k_pos = mem_pos[0]
        causal = False
    if "q_norm" in p:
        q = rmsnorm(q, p["q_norm"])
        k = rmsnorm(k, p["k_norm"])
    if cfg.rope and kv_memory is None:
        th = theta if theta is not None else cfg.rope_theta
        q = apply_rope(q, positions, th)
        k = apply_rope(k, positions, th)
    w = window if window is not None else GLOBAL_WINDOW
    bias = _mask_bias(positions[0], k_pos, w, causal)[None, None, None]
    qg = q.reshape(B, S, KH, G, hd)
    out = sdpa(qg, k, v, bias, cfg.attn_logit_softcap).reshape(B, S, H, hd)
    y = jnp.einsum("bshe,hed->bsd", out, p["wo"])
    if "bo" in p:
        y = y + p["bo"]
    return y, (k, v)


def attn_decode(
    p: Dict[str, jax.Array],
    x: jax.Array,  # (B, 1, d)
    cache: Tuple[jax.Array, jax.Array],  # k/v: (B, S_cache, KH, hd)
    cfg,
    cache_index: jax.Array,  # scalar int32 OR (B,) per-slot positions
    window=None,
    theta=None,
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One-token decode; writes the new KV at ``cache_index`` (ring-free).

    ``cache_index`` may be a scalar (whole batch at one position — the
    dry-run/serving fast path, lowered as dynamic_update_slice) or a (B,)
    vector (continuous batching: each slot at its own age, lowered as a
    per-row scatter; see serving/batching.py).
    """
    B = x.shape[0]
    H, KH, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    G = H // KH
    k_cache, v_cache = cache
    S = k_cache.shape[1]
    per_slot = jnp.ndim(cache_index) == 1
    idx_vec = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32).reshape(-1), (B,))
    pos = idx_vec[:, None]
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k_new = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
    v_new = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
    if "bq" in p:
        q, k_new, v_new = q + p["bq"], k_new + p["bk"], v_new + p["bv"]
    if "q_norm" in p:
        q = rmsnorm(q, p["q_norm"])
        k_new = rmsnorm(k_new, p["k_norm"])
    if cfg.rope:
        th = theta if theta is not None else cfg.rope_theta
        q = apply_rope(q, pos, th)
        k_new = apply_rope(k_new, pos, th)
    if per_slot:
        rows = jnp.arange(B)
        wr = jnp.minimum(idx_vec, S - 1)
        k_cache = k_cache.at[rows, wr].set(k_new[:, 0].astype(k_cache.dtype))
        v_cache = v_cache.at[rows, wr].set(v_new[:, 0].astype(v_cache.dtype))
    else:
        idx = jnp.minimum(jnp.asarray(cache_index, jnp.int32), S - 1)
        k_cache = jax.lax.dynamic_update_slice(k_cache, k_new.astype(k_cache.dtype), (0, idx, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(v_cache, v_new.astype(v_cache.dtype), (0, idx, 0, 0))
    w = window if window is not None else GLOBAL_WINDOW
    k_pos = jnp.arange(S, dtype=jnp.int32)
    valid = (k_pos[None, :] <= idx_vec[:, None]) & ((idx_vec[:, None] - k_pos[None, :]) < w)
    bias = jnp.where(valid, 0.0, _NEG_INF)[:, None, None, None, :]
    qg = q.reshape(B, 1, KH, G, hd)
    out = sdpa(qg, k_cache.astype(q.dtype), v_cache.astype(q.dtype), bias, cfg.attn_logit_softcap)
    y = jnp.einsum("bshe,hed->bsd", out.reshape(B, 1, H, hd), p["wo"])
    if "bo" in p:
        y = y + p["bo"]
    return y, (k_cache, v_cache)


# --------------------------------------------------------------- MLA paths
def _mla_qkv(p, x, cfg, positions):
    m = cfg.mla
    H = cfg.n_heads
    q = jnp.einsum("bsd,dr->bsr", x, p["wq_a"])
    q = rmsnorm(q, p["q_norm"])
    q = jnp.einsum("bsr,rhe->bshe", q, p["wq_b"])  # (B,S,H,nope+rope)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    c_kv, k_rope = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    c_kv = rmsnorm(c_kv, p["kv_norm"])
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)[:, :, 0, :]
    return q_nope, q_rope, c_kv, k_rope


def mla_forward(
    p, x: jax.Array, cfg, positions: jax.Array
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Train/prefill MLA with expanded per-head K/V (standard formulation)."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, x, cfg, positions)
    kv = jnp.einsum("bsr,rhe->bshe", c_kv, p["wkv_b"])
    k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, S, H, m.qk_rope_head_dim))], -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    bias = _mask_bias(positions[0], positions[0], GLOBAL_WINDOW, True)[None, None, None]
    out = sdpa(q.reshape(B, S, H, 1, -1), k, v, bias)
    y = jnp.einsum("bshe,hed->bsd", out.reshape(B, S, H, m.v_head_dim), p["wo"])
    # cache = compressed latent + shared rope key (absorbed decode reads these)
    return y, (c_kv, k_rope)


def mla_decode(
    p, x: jax.Array, cache: Tuple[jax.Array, jax.Array], cfg, cache_index: jax.Array
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Absorbed MLA decode: latent cache only, no per-head K/V materialized."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    c_cache, r_cache = cache  # (B,S,kv_lora), (B,S,rope)
    S = c_cache.shape[1]
    per_slot = jnp.ndim(cache_index) == 1
    idx_vec = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32).reshape(-1), (B,))
    pos = idx_vec[:, None]
    q_nope, q_rope, c_new, r_new = _mla_qkv(p, x, cfg, pos)
    if per_slot:
        rows = jnp.arange(B)
        wr = jnp.minimum(idx_vec, S - 1)
        c_cache = c_cache.at[rows, wr].set(c_new[:, 0].astype(c_cache.dtype))
        r_cache = r_cache.at[rows, wr].set(r_new[:, 0].astype(r_cache.dtype))
    else:
        idx = jnp.minimum(jnp.asarray(cache_index, jnp.int32), S - 1)
        c_cache = jax.lax.dynamic_update_slice(c_cache, c_new.astype(c_cache.dtype), (0, idx, 0))
        r_cache = jax.lax.dynamic_update_slice(r_cache, r_new.astype(r_cache.dtype), (0, idx, 0))
    # absorb q through W_UK:  (B,1,H,nope) x (r,H,nope) -> (B,H,r)
    w_uk = p["wkv_b"][..., : m.qk_nope_head_dim]
    w_uv = p["wkv_b"][..., m.qk_nope_head_dim :]
    q_lat = jnp.einsum("bshe,rhe->bhr", q_nope, w_uk)
    scale = 1.0 / jnp.sqrt(jnp.float32(m.qk_nope_head_dim + m.qk_rope_head_dim))
    c = c_cache.astype(x.dtype)
    r = r_cache.astype(x.dtype)
    scores = jnp.einsum("bhr,bsr->bhs", q_lat, c, preferred_element_type=jnp.float32)
    scores += jnp.einsum("bshe,bte->bht", q_rope, r, preferred_element_type=jnp.float32)
    k_pos = jnp.arange(S, dtype=jnp.int32)
    valid = k_pos[None, :] <= idx_vec[:, None]  # (B, S)
    scores = jnp.where(valid[:, None, :], scores * scale, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o_lat = jnp.einsum("bhs,bsr->bhr", probs, c)
    out = jnp.einsum("bhr,rhe->bhe", o_lat, w_uv)  # (B,H,v_head)
    y = jnp.einsum("bhe,hed->bd", out, p["wo"])[:, None, :]
    return y, (c_cache, r_cache)
