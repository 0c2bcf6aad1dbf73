"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(``interpret=True`` executes the kernel bodies on CPU).  Hypothesis property
tests live in test_kernels_properties.py so this module runs even where
hypothesis isn't installed."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.jax_sched import JIQState, sched_many_fused
from repro.kernels import ops, ref, sched_step

jax.config.update("jax_enable_x64", False)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,KH,hd,causal,window",
    [
        (1, 128, 4, 4, 64, True, None),     # MHA causal
        (2, 256, 8, 2, 64, True, None),     # GQA
        (1, 256, 4, 1, 128, True, 64),      # MQA + sliding window
        (2, 128, 4, 4, 32, False, None),    # bidirectional (whisper encoder)
    ],
)
def test_flash_attention_sweep(B, S, H, KH, hd, causal, window, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KH, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KH, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window, block_q=64, block_k=64,
                              interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


# ------------------------------------------------------------ decode attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,KH,hd,valid,window",
    [
        (2, 512, 8, 2, 64, 511, None),
        (1, 256, 4, 4, 128, 100, None),
        (2, 512, 16, 2, 64, 300, 128),   # SWA decode
        (1, 128, 8, 1, 64, 0, None),     # first token
    ],
)
def test_decode_attention_sweep(B, S, H, KH, hd, valid, window, dtype):
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (B, H, hd), dtype)
    kc = jax.random.normal(ks[1], (B, S, KH, hd), dtype)
    vc = jax.random.normal(ks[2], (B, S, KH, hd), dtype)
    out = ops.decode_attention(q, kc, vc, jnp.int32(valid), window=window, block_k=128,
                               interpret=True)
    want = ref.decode_attention_ref(q, kc, vc, jnp.int32(valid), window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32), **_tol(dtype)
    )


def test_decode_attention_matches_flash_last_row():
    """Decode of the last position == last row of full flash attention."""
    ks = jax.random.split(jax.random.key(2), 3)
    B, S, H, KH, hd = 1, 128, 4, 2, 32
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KH, hd))
    v = jax.random.normal(ks[2], (B, S, KH, hd))
    full = ref.flash_attention_ref(q, k, v, causal=True)
    dec = ops.decode_attention(q[:, -1], k, v, jnp.int32(S - 1), block_k=64,
                               interpret=True)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full[:, -1]), atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------------- SSD scan
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,P,N,chunk,block_h",
    [
        (2, 256, 8, 16, 32, 64, 4),
        (1, 128, 24, 64, 128, 64, 8),   # mamba2-130m dims
        (1, 64, 4, 16, 16, 64, 4),      # single chunk
        (2, 192, 6, 16, 32, 64, 6),     # H % block_h fallback
    ],
)
def test_ssd_scan_sweep(B, S, H, P, N, chunk, block_h, dtype):
    ks = jax.random.split(jax.random.key(3), 5)
    x = (jax.random.normal(ks[0], (B, S, H, P)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = (jax.random.normal(ks[3], (B, S, 1, N)) * 0.3).astype(dtype)
    Cm = (jax.random.normal(ks[4], (B, S, 1, N)) * 0.3).astype(dtype)
    y, st = ops.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, block_h=block_h,
                         interpret=True)
    yr, sr = ref.ssd_scan_ref(
        x.astype(jnp.float32), dt, A, Bm.astype(jnp.float32), Cm.astype(jnp.float32), chunk
    )
    tol = dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else dict(atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(yr, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(st), np.asarray(sr), **tol)


def test_ssd_scan_state_carry_equals_two_halves():
    """Scanning S tokens == scanning S/2 then S/2 with carried state."""
    ks = jax.random.split(jax.random.key(4), 5)
    B, S, H, P, N, chunk = 1, 128, 4, 16, 16, 32
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, 1, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, 1, N)) * 0.3
    y_full, st_full = ref.ssd_scan_ref(x, dt, A, Bm, Cm, chunk)
    h = S // 2
    y1, st1 = ref.ssd_scan_ref(x[:, :h], dt[:, :h], A, Bm[:, :h], Cm[:, :h], chunk)
    y2, st2 = ref.ssd_scan_ref(x[:, h:], dt[:, h:], A, Bm[:, h:], Cm[:, h:], chunk, init_state=st1)
    np.testing.assert_allclose(np.asarray(y_full[:, h:]), np.asarray(y2), atol=1e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(st_full), np.asarray(st2), atol=1e-4, rtol=1e-3)


# ------------------------------------------------- fused mixed-event dispatch
@pytest.mark.parametrize(
    "R,F,W,arrivals_only",
    [
        (32, 4, 8, False), (100, 10, 16, False), (57, 3, 5, False),
        (128, 40, 130, False),
        # ARRIVAL-only bursts: Algorithm 1's vectorized burst
        (16, 4, 8, True), (64, 10, 16, True), (8, 1, 4, True),
        (128, 40, 5, True), (48, 6, 8, True),
    ],
)
def test_sched_events_sweep(R, F, W, arrivals_only):
    """Fused (ARRIVAL|FINISH|EVICT) kernel == the jax_sched scan oracle, and
    on ARRIVAL-only bursts == ``ref.sched_step_ref`` as well."""
    rng = np.random.default_rng(R * 1000 + W)
    kinds = np.zeros(R, np.int32) if arrivals_only else rng.integers(0, 3, R)
    funcs = rng.integers(0, F, R)
    workers = np.where(kinds == 0, -1, rng.integers(0, W, R))
    idle = rng.integers(0, 3, (F, W))
    conns = rng.integers(0, 5, W)
    args = [jnp.asarray(a, jnp.int32) for a in (kinds, funcs, workers, idle, conns)]
    a, warm, i2, c2 = ops.sched_events(*args, interpret=True)
    ar, wr, ir, cr = ref.sched_events_ref(*args)
    assert jnp.all(a == ar) and jnp.all(warm == wr)
    assert jnp.all(i2 == ir) and jnp.all(c2 == cr)
    if arrivals_only:
        ar, wr, ir, cr = ref.sched_step_ref(args[1], args[3], args[4])
        assert jnp.all(a == ar) and jnp.all(warm == wr.astype(jnp.int32))
        assert jnp.all(i2 == ir) and jnp.all(c2 == cr)


def test_sched_events_native_guards(monkeypatch):
    """The native kernel never runs elsewhere: off the TPU it raises naming
    the backend, and a table wider than the VMEM cap raises naming the
    width instead of running another path."""
    shapes = [jax.ShapeDtypeStruct(s, jnp.int32) for s in [(8,), (8,), (8,)]]
    with pytest.raises(RuntimeError, match="'cpu'"):
        ops.sched_events(*shapes, jnp.zeros((4, 8), jnp.int32),
                         jnp.zeros((8,), jnp.int32))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    F = 40
    W = next(w for w in range(128, 10**6, 128)
             if sched_step.vmem_bytes(F, w) > ops.SCHED_VMEM_CAP_BYTES)
    assert sched_step.vmem_bytes(F, W - 128) <= ops.SCHED_VMEM_CAP_BYTES
    with pytest.raises(ValueError, match=f"{W} workers"):
        ops.sched_events(*shapes, jax.ShapeDtypeStruct((F, W), jnp.int32),
                         jax.ShapeDtypeStruct((W,), jnp.int32))
    # the whole-stream wrapper checks the same before its one program
    wide = JIQState(jax.ShapeDtypeStruct((F, W), jnp.int32),
                    jax.ShapeDtypeStruct((W,), jnp.int32))
    with pytest.raises(ValueError, match=f"sched_many_fused: {F} functions x {W} workers"):
        sched_many_fused(wide, jnp.zeros((8, 3), jnp.int32))
