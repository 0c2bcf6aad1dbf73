"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracles
(``interpret=True`` executes the kernel bodies on CPU).  Hypothesis property
tests live in test_kernels_properties.py so this module runs even where
hypothesis isn't installed."""

import runpy
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.jax_sched import JIQState, sched_many, sched_many_fused
from repro.kernels import ops, ref, sched_step

jax.config.update("jax_enable_x64", False)

# the chip benchmark's plain reference: Algorithm 1 by whole-array scans,
# importing nothing of the program
brute_force = runpy.run_path(
    str(Path(__file__).resolve().parents[1] / "benchmarks" / "chip" / "reference.py")
)["brute_force"]


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
def _scan(kinds, funcs, workers, idle, conns):
    """The burst through ``sched_many``'s event-by-event scan, in the fused
    call's output order: ``(assign, warm, idle', conns')``."""
    events = jnp.stack([kinds, funcs, workers], axis=1)
    state, (ws, warm) = sched_many(JIQState(idle, conns), events)
    return ws, warm, state.idle, state.conns


def _fused(kinds, funcs, workers, idle, conns):
    """The burst through ``sched_many_fused`` in interpret mode, one launch
    of the form the table's shape picks, in :func:`_scan`'s order."""
    events = jnp.stack([kinds, funcs, workers], axis=1)
    state, (ws, warm) = sched_many_fused(JIQState(idle, conns), events, interpret=True)
    return ws, warm, state.idle, state.conns


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
    on ARRIVAL-only bursts == ``ref.sched_step_ref`` as well.  The VMEM
    form pads W to whole 128-lane rows with +INF connections, in interpret
    mode as on the chip."""
    assert sched_step.table_form("test", (F, W), interpret=True) == "vmem"
    rng = np.random.default_rng(R * 1000 + W)
    kinds = np.zeros(R, np.int32) if arrivals_only else rng.integers(0, 3, R)
    funcs = rng.integers(0, F, R)
    workers = np.where(kinds == 0, -1, rng.integers(0, W, R))
    idle = rng.integers(0, 3, (F, W))
    conns = rng.integers(0, 5, W)
    args = [jnp.asarray(a, jnp.int32) for a in (kinds, funcs, workers, idle, conns)]
    a, warm, i2, c2 = _fused(*args)
    ar, wr, ir, cr = _scan(*args)
    assert jnp.all(a == ar) and jnp.all(warm == wr)
    assert jnp.all(i2 == ir) and jnp.all(c2 == cr)
    if arrivals_only:
        ar, wr, ir, cr = ref.sched_step_ref(args[1], args[3], args[4])
        assert jnp.all(a == ar) and jnp.all(warm == wr)
        assert jnp.all(i2 == ir) and jnp.all(c2 == cr)


@pytest.mark.parametrize(
    "R,F,W",
    [(64, 1, 5), (65, 24, 130), (63, 40, 200), (160, 40, 5), (1, 24, 200),
     (100, 1, 130), (160, 300, 13), (70, 260, 200)],
)
def test_sched_events_hbm_sweep(R, F, W, monkeypatch):
    """The HBM form (the table left in HBM, the part of its function's row
    an event needs brought in per event) == the jax_sched scan oracle and
    the benchmark's plain reference, on mixed streams of all four kinds (3
    is a no-op), with W off the 8-row tiles and F off the 128-lane tiles.
    A VMEM cap just below the VMEM form's need makes ``sched_many_fused``
    take it (interpret mode holds the HBM form to no cap)."""
    monkeypatch.setattr(sched_step, "VMEM_CAP_BYTES", sched_step.vmem_bytes(F, W) - 1)
    assert sched_step.table_form("test", (F, W), interpret=True) == "hbm"
    rng = np.random.default_rng(R * 1000 + F * 10 + W)
    kinds = rng.integers(0, 4, R)
    funcs = rng.integers(0, F, R)
    workers = np.where(kinds == 0, -1, rng.integers(0, W, R))
    idle = rng.integers(0, 3, (F, W))
    conns = rng.integers(0, 5, W)
    args = [jnp.asarray(a, jnp.int32) for a in (kinds, funcs, workers, idle, conns)]
    assert "_sched_events_hbm" in str(jax.make_jaxpr(_fused)(*args))
    got = [np.asarray(x) for x in _fused(*args)]
    want = [np.asarray(x) for x in _scan(*args)]
    plain = brute_force(idle, conns, np.stack([kinds, funcs, workers], 1))
    for g, w, b in zip(got, want, plain):
        assert np.array_equal(g, w) and np.array_equal(g, np.asarray(b, g.dtype))


def _resident_stream(F, W, seed):
    """A stream over the several 128-function column blocks of ``F``
    functions that takes every path of the HBM kernel's resident slots: a
    FINISH and an EVICT on blocks before their first ARRIVAL, the first
    blocks in and repeat ARRIVALs on them, an EVICT of a zero count and
    notices once they are resident, then ARRIVALs on every other block
    (through the spill slot once the slots are taken) with a notice after
    each block's, and a mixed tail of all four kinds.  Returns ``(kinds,
    funcs, workers, idle, conns)``."""
    rng = np.random.default_rng(seed)
    n_blocks = -(-F // 128)
    fn = lambda b: min(b * 128 + int(rng.integers(128)), F - 1)  # noqa: E731
    wk = lambda: int(rng.integers(W))  # noqa: E731
    idle = rng.integers(0, 3, (F, W))
    conns = rng.integers(0, 5, W)
    ev = []
    for b in (1, n_blocks - 1):
        ev += [(1, fn(b), wk()), (2, fn(b), wk())]
    ev += [(0, fn(b), -1) for b in (0, 1, 0, 0, 1)]
    f0, w0 = fn(0), wk()
    idle[f0, w0] = 0
    ev.append((2, f0, w0))  # nothing before it enqueues (f0, w0)
    for b in (0, 1):
        ev += [(1, fn(b), wk()), (2, fn(b), wk())]
    for b in range(2, n_blocks):
        ev += [(0, fn(b), -1), (0, fn(b), -1), (1, fn(b), wk())]
    for _ in range(60):
        k = int(rng.integers(4))
        ev.append((k, int(rng.integers(F)), -1 if k == 0 else wk()))
    kinds, funcs, workers = np.array(ev, np.int32).T
    return kinds, funcs, workers, idle, conns


@pytest.mark.parametrize("W", [13, 130, 200])
@pytest.mark.parametrize("slots", [1, 2])
def test_sched_events_hbm_resident_slots(W, slots):
    """The HBM form with fewer resident slots than the table has column
    blocks (700 functions: 6 blocks) == the jax_sched scan oracle and the
    benchmark's plain reference, answers and state, on a stream that runs
    resident hits, write-through notices, a zero-count EVICT in a resident
    block and ARRIVALs through the spill slot."""
    F = 700
    kinds, funcs, workers, idle, conns = _resident_stream(F, W, seed=W * 10 + slots)
    loads, resident = sched_step.hbm_row_loads(kinds, funcs, slots, len(kinds))
    assert resident > 0 and loads > 0
    args = [jnp.asarray(a, jnp.int32) for a in (kinds, funcs, workers, idle, conns)]
    a, warm, i2, c2 = sched_step.sched_events_hbm(
        *args[:4], args[4][None, :], slots=slots, interpret=True)
    got = [np.asarray(x) for x in (a, warm.astype(bool), i2, c2[0])]
    want = [np.asarray(x) for x in _scan(*args)]
    plain = brute_force(idle, conns, np.stack([kinds, funcs, workers], 1))
    for g, w, b in zip(got, want, plain):
        assert np.array_equal(g, w) and np.array_equal(g, np.asarray(b, g.dtype))


def test_sched_events_native_guards(monkeypatch):
    """The native kernel never runs elsewhere: off the TPU it raises naming
    the backend.  A table wider than the VMEM form's cap takes the HBM
    form, and a width at which even one resident column block (beside the
    staging and spill blocks) exceeds the cap raises naming the width
    instead of running another path."""
    events = jnp.zeros((8, 3), jnp.int32)
    with pytest.raises(RuntimeError, match="'cpu'"):
        sched_many_fused(JIQState(jnp.zeros((4, 8), jnp.int32), jnp.zeros((8,), jnp.int32)),
                         events)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    W = 5000
    F = next(f for f in range(8, 10**5, 8)
             if sched_step.vmem_bytes(f, W) > sched_step.VMEM_CAP_BYTES)
    assert sched_step.table_form("t", (F - 8, W), interpret=False) == "vmem"
    assert sched_step.table_form("t", (F, W), interpret=False) == "hbm"
    native = lambda idle, conns, ev: sched_many_fused(JIQState(idle, conns), ev)  # noqa: E731
    jaxpr = str(jax.make_jaxpr(native)(jax.ShapeDtypeStruct((F, W), jnp.int32),
                                       jax.ShapeDtypeStruct((W,), jnp.int32), events))
    assert "_sched_events_hbm" in jaxpr
    # the published 8,192 x 5,000 cluster is past the VMEM form and inside the HBM form
    assert sched_step.table_form("t", (8192, 5000), interpret=False) == "hbm"
    wide = next(w for w in range(W, 10**7, 128)
                if sched_step.hbm_vmem_bytes(w, slots=1) > sched_step.VMEM_CAP_BYTES)
    with pytest.raises(ValueError, match=f"{wide} workers"):
        sched_step.table_form("t", (F, wide), interpret=False)
    # the whole-stream wrapper checks the same before its one program
    state = JIQState(jax.ShapeDtypeStruct((F, wide), jnp.int32),
                     jax.ShapeDtypeStruct((wide,), jnp.int32))
    with pytest.raises(ValueError, match=f"sched_many_fused: {F} functions x {wide} workers"):
        sched_many_fused(state, events)


_FORBIDDEN = ("repro.models", "repro.sharding", "repro.kernels.ops", "repro.kernels.ref")


@pytest.mark.parametrize("code", [
    "import repro.kernels.sched_step",
    "import repro.core.jax_sched",
    "import numpy as np\n"
    "from repro.core.jax_sched import init_state, sched_many_fused\n"
    "ev = np.array([[0, 1, -1], [1, 1, 2], [2, 0, 1]], np.int32)\n"
    "sched_many_fused(init_state(3, 5), ev, interpret=True)[1][0].block_until_ready()",
], ids=["sched_step", "jax_sched", "fused-call"])
def test_dispatch_path_loads_no_model_code(code):
    """The dispatch path (``core.jax_sched`` -> ``kernels.sched_step``)
    loads none of the LLM stack: no model, sharding, ``ops`` or ``ref``
    module is imported by the kernel, its wrapper or a call through it."""
    import os
    import subprocess
    import sys

    import repro

    src = os.path.dirname(os.path.dirname(repro.__file__))
    probe = code + (
        "\nimport sys\n"
        f"print(sorted(m for m in sys.modules if m.startswith({_FORBIDDEN!r})))"
    )
    out = subprocess.run(
        [sys.executable, "-c", probe], capture_output=True, text=True, timeout=300,
        check=True, env={**os.environ, "PYTHONPATH": src, "JAX_PLATFORMS": "cpu"},
    )
    assert out.stdout.strip().splitlines()[-1] == "[]"
