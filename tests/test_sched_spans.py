"""The profiler spans and the compile count inside ``sched_many_fused``.

The kernel path of ``core.jax_sched.sched_many_fused`` writes
``jax.profiler.TraceAnnotation`` spans into the profiler's trace, on the
same clock as the device's operations: ``sched.fused`` per call, with its
``chunks``, ``events``, ``padded`` and ``compiles`` as metadata, and inside
it one ``sched.stage_in``, one ``sched.launch`` (the call's one jitted
program, whatever its number of chunks) and one ``sched.stage_out``.
``sched.fused`` also names the kernel's form (``table``) and, for a host
burst, the loads of function rows the kernel makes from HBM (``row_loads``)
and, on the HBM form, the events it serves from a column block it keeps in
VMEM (``resident``).
These tests trace calls on the CPU, with the kernel in Pallas's
interpreter, and read the trace back with ``jax.profiler.ProfileData``.
"""

import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from repro.core import jax_sched
from repro.core.jax_sched import ARRIVAL, EVICT, FINISH, JIQState, sched_many_fused
from repro.kernels import sched_step
from repro.utils.compile_cache import compile_count

CHIP_BENCH = Path(__file__).resolve().parents[1] / "benchmarks" / "chip"

STAGES = ("sched.stage_in", "sched.launch", "sched.stage_out")


def _events(n, n_funcs, n_workers, seed, kinds=(ARRIVAL, FINISH, EVICT),
            p=(0.5, 0.4, 0.1)):
    """A mixed stream whose FINISH/EVICT notices name any worker: the
    scheduler's answers need not be meaningful, only equal run to run."""
    rng = np.random.default_rng(seed)
    kinds = rng.choice(kinds, size=n, p=p)
    funcs = rng.integers(0, n_funcs, n)
    workers = np.where(kinds == ARRIVAL, -1, rng.integers(0, n_workers, n))
    return np.stack([kinds, funcs, workers], 1).astype(np.int32)


def _state(n_funcs, n_workers):
    return JIQState(jnp.zeros((n_funcs, n_workers), jnp.int32),
                    jnp.zeros((n_workers,), jnp.int32))


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; returns its result and the host
    events named ``sched.*``, ``(name, start_ns, end_ns, stats)`` in start
    order."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
        jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    (path,) = Path(tmp_path).glob("**/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
             for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events
             if ev.name.startswith("sched.")]
    return out, sorted(spans, key=lambda s: s[1])


def test_one_call_leaves_one_fused_span_with_its_stages_inside(tmp_path):
    state, ev = _state(5, 11), _events(2500, 5, 11, seed=1)
    _, spans = _traced(tmp_path, lambda: sched_many_fused(state, ev, interpret=True))
    fused = [s for s in spans if s[0] == "sched.fused"]
    assert len(fused) == 1
    _, lo, hi, stats = fused[0]
    assert {k: stats[k] for k in ("chunks", "events", "padded")} == {
        "chunks": 3, "events": 2500, "padded": 0}
    names = [s[0] for s in spans if s[0] != "sched.fused"]
    assert names == list(STAGES)
    assert all(lo <= s[1] <= s[2] <= hi for s in spans)


def test_a_stream_length_compiles_one_program_once(monkeypatch):
    """A first call at a length (and table shape) no other test uses
    compiles the call's one program, kernel and all; the same length again
    compiles nothing, whatever the events."""
    monkeypatch.setattr(jax_sched, "CHUNK", 32)
    count = compile_count()
    state = jax.block_until_ready(_state(7, 13))
    made = []
    for seed in (4, 5, 6):
        before = count.compiles
        jax.block_until_ready(sched_many_fused(
            state, _events(77, 7, 13, seed), interpret=True))
        made.append(count.compiles - before)
    assert made == [1, 0, 0]


def test_compiles_are_counted_in_the_call_that_makes_them(tmp_path, monkeypatch):
    """A first call at a table shape no other test uses compiles; the same
    call again compiles nothing."""
    monkeypatch.setattr(jax_sched, "CHUNK", 256)
    state, ev = _state(3, 17), _events(300, 3, 17, seed=2)

    def twice():
        first = sched_many_fused(state, ev, interpret=True)
        jax.block_until_ready(first)
        return sched_many_fused(state, ev, interpret=True)

    _, spans = _traced(tmp_path, twice)
    first, again = [s[3] for s in spans if s[0] == "sched.fused"]
    assert first["compiles"] > 0
    assert again["compiles"] == 0
    assert first["padded"] == again["padded"] == 0


@pytest.mark.parametrize("n,chunk", [(192, 64), (64, 64), (150, 64), (20, 64)],
                         ids=["whole-chunks", "one-chunk", "ragged-tail", "tail-only"])
def test_chunks_counts_the_launches_and_nothing_is_padded(tmp_path, monkeypatch, n, chunk):
    """Each launch runs exactly the events it is given: ``chunks`` is
    ``ceil(n / chunk)`` (``n / chunk`` where it divides) and ``padded`` is
    0, with or without a ragged tail."""
    monkeypatch.setattr(jax_sched, "CHUNK", chunk)
    state, ev = _state(6, 9), _events(n, 6, 9, seed=11)
    _, spans = _traced(tmp_path, lambda: sched_many_fused(state, ev, interpret=True))
    (stats,) = [s[3] for s in spans if s[0] == "sched.fused"]
    assert {k: stats[k] for k in ("chunks", "events", "padded")} == {
        "chunks": -(-n // chunk), "events": n, "padded": 0}


def test_answers_and_state_are_the_same_with_a_trace_active(tmp_path, monkeypatch):
    monkeypatch.setattr(jax_sched, "CHUNK", 512)
    state, ev = _state(4, 9), _events(700, 4, 9, seed=3)
    call = lambda: sched_many_fused(state, ev, interpret=True)  # noqa: E731
    plain = jax.device_get(call())
    traced, spans = _traced(tmp_path, call)
    assert [s[0] for s in spans].count("sched.fused") == 1
    for got, want in zip(jax.tree.leaves(jax.device_get(traced)), jax.tree.leaves(plain)):
        assert got.dtype == want.dtype and np.array_equal(got, want)


def test_the_compile_count_counts_a_fresh_jit_compile():
    count = compile_count()
    assert compile_count() is count
    x = jax.block_until_ready(jnp.arange(13, dtype=jnp.float32))
    compiles, seconds = count.compiles, count.seconds
    jax.block_until_ready(jax.jit(lambda v: v * 3.0 + 0.25)(x))
    assert count.compiles == compiles + 1
    assert count.seconds > seconds


def _in_hbm(monkeypatch, n_funcs, n_workers):
    """A VMEM cap just below the VMEM form's need, so a small table takes
    the HBM form (interpret mode holds that form to no cap)."""
    monkeypatch.setattr(sched_step, "VMEM_CAP_BYTES", sched_step.vmem_bytes(n_funcs, n_workers) - 1)


def _walk(kinds, funcs, slots, chunk):
    """The HBM kernel's rule of loads, event by event: in each launch of
    ``chunk`` events the first ``slots`` distinct column blocks that
    ARRIVALs bring in stay resident; a real event on one after its first
    ARRIVAL is ``resident``, any other real event a row load.  Returns
    ``(row_loads, resident)``."""
    loads = resident = 0
    for start in range(0, len(kinds), chunk):
        kept = set()
        for k, f in zip(kinds[start:start + chunk], funcs[start:start + chunk]):
            if k not in (ARRIVAL, FINISH, EVICT):
                continue
            if f // 128 in kept:
                resident += 1
                continue
            loads += 1
            if k == ARRIVAL and len(kept) < slots:
                kept.add(f // 128)
    return loads, resident


@pytest.mark.parametrize("table", ["vmem", "hbm"])
def test_fused_span_names_the_table_form_and_its_row_loads(tmp_path, monkeypatch, table):
    """``table`` names the form the shape chose.  On the HBM form a host
    burst's ``row_loads`` and ``resident`` split its ARRIVAL, FINISH and
    EVICT events (no-op kind 3 counts in neither) by the kernel's rule; the
    VMEM form reads ``row_loads`` 0 and no ``resident``.  A device burst
    leaves both out rather than fetch it."""
    monkeypatch.setattr(jax_sched, "CHUNK", 64)
    if table == "hbm":
        _in_hbm(monkeypatch, 24, 130)
    state = _state(24, 130)
    ev = _events(150, 24, 130, seed=7, kinds=(ARRIVAL, FINISH, EVICT, 3),
                 p=(0.4, 0.3, 0.1, 0.2))
    loads = int(np.isin(ev[:, 0], (ARRIVAL, FINISH, EVICT)).sum())
    assert 0 < loads < len(ev)

    def calls():
        host = sched_many_fused(state, ev, interpret=True)
        jax.block_until_ready(host)
        return sched_many_fused(state, jnp.asarray(ev), interpret=True)

    _, spans = _traced(tmp_path, calls)
    host, device = [s[3] for s in spans if s[0] == "sched.fused"]
    assert host["table"] == device["table"] == table
    if table == "hbm":
        got = (int(host["row_loads"]), int(host["resident"]))
        assert got == _walk(ev[:, 0], ev[:, 1], sched_step.hbm_slots(24, 130), 64)
        assert sum(got) == loads and got[1] > 0
    else:
        assert int(host["row_loads"]) == 0 and "resident" not in host
    assert "row_loads" not in device and "resident" not in device


def test_row_loads_are_the_rows_the_kernel_loads(monkeypatch):
    """On a seeded one-chunk stream over three column blocks with one
    resident slot, the span's counts are what the HBM kernel moves: the TPU
    interpreter numbers every DMA a kernel starts, and the kernel starts
    one whole-table copy, then per row load a load and a store (an
    ARRIVAL's column block in and the tile it changes out, a FINISH's or
    EVICT's tile in and out) and per resident event one store (the tile it
    changes, built in VMEM)."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as interpreter

    monkeypatch.setattr(jax_sched, "CHUNK", 256)
    _in_hbm(monkeypatch, 300, 200)
    state = _state(300, 200)
    assert sched_step.hbm_slots(300, 200) == 1

    def dmas(ev):
        pltpu.reset_tpu_interpret_mode_state()
        jax.block_until_ready(sched_many_fused(state, ev, interpret=True))
        return interpreter.dma_id_counter.value

    noop = np.zeros((200, 3), np.int32)
    noop[:, 0] = 3
    ev = _events(200, 300, 200, seed=8, kinds=(ARRIVAL, FINISH, EVICT, 3),
                 p=(0.4, 0.3, 0.1, 0.2))
    loads, resident = sched_step.hbm_row_loads(ev[:, 0], ev[:, 1], 1, 256)
    assert loads > 0 and resident > 0
    assert dmas(ev) - dmas(noop) == 2 * loads + resident
    assert loads + resident == int((ev[:, 0] != 3).sum())


@pytest.mark.parametrize("seed", range(6))
def test_the_load_count_is_the_rule_walked_event_by_event(seed):
    """``sched_step.hbm_row_loads`` (vectorised, as the timed call needs)
    equals a plain walk of the kernel's rule on seeded streams of several
    launches, with fewer slots than blocks, as many, and more, and with
    skewed functions so blocks repeat."""
    rng = np.random.default_rng(seed)
    n, n_funcs = int(rng.integers(1, 900)), int(rng.integers(1, 1100))
    ev = _events(n, n_funcs, 7, seed, kinds=(ARRIVAL, FINISH, EVICT, 3), p=(0.4, 0.3, 0.1, 0.2))
    ev[:, 1] = np.minimum(ev[:, 1], rng.geometric(0.004, n) - 1)
    n_blocks = -(-n_funcs // 128)
    for slots in sorted({1, max(1, n_blocks // 2), n_blocks, n_blocks + 2}):
        for chunk in (int(rng.integers(1, 64)), 128, 1024):
            assert sched_step.hbm_row_loads(ev[:, 0], ev[:, 1], slots, chunk) == _walk(
                ev[:, 0], ev[:, 1], slots, chunk)


def test_a_call_of_several_launches_counts_each_launchs_slots(tmp_path, monkeypatch):
    """The span of a call of several launches (``CHUNK`` cut short) counts
    each launch's resident blocks from scratch, as the kernel keeps them
    per launch: its ``row_loads`` and ``resident`` equal the walked rule."""
    monkeypatch.setattr(jax_sched, "CHUNK", 48)
    _in_hbm(monkeypatch, 400, 13)
    ev = _events(170, 400, 13, seed=12, kinds=(ARRIVAL, FINISH, EVICT, 3),
                 p=(0.4, 0.3, 0.1, 0.2))
    _, spans = _traced(tmp_path, lambda: sched_many_fused(_state(400, 13), ev, interpret=True))
    (stats,) = [s[3] for s in spans if s[0] == "sched.fused"]
    assert int(stats["chunks"]) == 4
    got = (int(stats["row_loads"]), int(stats["resident"]))
    assert got == _walk(ev[:, 0], ev[:, 1], sched_step.hbm_slots(400, 13), 48)
    assert got[1] > 0


def test_hbm_answers_and_state_are_the_same_with_a_trace_active(tmp_path, monkeypatch):
    monkeypatch.setattr(jax_sched, "CHUNK", 128)
    _in_hbm(monkeypatch, 24, 130)
    state, ev = _state(24, 130), _events(300, 24, 130, seed=9)
    call = lambda: sched_many_fused(state, ev, interpret=True)  # noqa: E731
    plain = jax.device_get(call())
    traced, spans = _traced(tmp_path, call)
    assert [s[0] for s in spans].count("sched.fused") == 1
    for got, want in zip(jax.tree.leaves(jax.device_get(traced)), jax.tree.leaves(plain)):
        assert got.dtype == want.dtype and np.array_equal(got, want)


def test_the_benchmark_reads_ns_per_row_load_from_the_spans(tmp_path, monkeypatch):
    """The chip benchmark's ``sched_kernel.ns_per_row_load`` divides the
    kernel's device time by the ``row_loads`` of the window's calls, and
    reads nothing where the calls load no rows (a table in VMEM)."""
    import importlib.util

    monkeypatch.syspath_prepend(str(CHIP_BENCH))
    spec = importlib.util.spec_from_file_location(
        "ns_per_row_load", CHIP_BENCH / "metrics" / "sched_kernel.ns_per_row_load.py")
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)

    def ctx(window):
        trace = types.SimpleNamespace(
            window=window, op_ns=lambda match: (6.0e6, 3) if match(
                "_sched_events_hbm.1 custom-call") else (0.0, 0))
        return types.SimpleNamespace(trace=trace)

    monkeypatch.setattr(jax_sched, "CHUNK", 128)
    ev = _events(100, 24, 130, seed=10)
    _, spans = _traced(tmp_path / "vmem", lambda: sched_many_fused(
        _state(24, 130), ev, interpret=True))
    whole = (spans[0][1], spans[-1][2])
    assert reader.read(ctx(whole), tmp_path / "vmem") is None
    _in_hbm(monkeypatch, 24, 130)

    def two_calls():
        first = sched_many_fused(_state(24, 130), ev, interpret=True)
        return sched_many_fused(first[0], ev[:40], interpret=True)

    _, spans = _traced(tmp_path / "hbm", two_calls)
    fused = [s for s in spans if s[0] == "sched.fused"]
    slots = sched_step.hbm_slots(24, 130)
    first, second = (_walk(e[:, 0], e[:, 1], slots, 128)[0] for e in (ev, ev[:40]))
    assert reader.read(ctx((fused[0][1], fused[1][2])), tmp_path / "hbm") == 6.0e6 / (
        first + second)
    assert reader.read(ctx((fused[1][1], fused[1][2])), tmp_path / "hbm") == 6.0e6 / second


def test_the_benchmark_reads_resident_share_from_the_spans(tmp_path, monkeypatch):
    """The chip benchmark's ``sched_kernel.resident_share`` is the window's
    summed ``resident`` over its summed ``resident + row_loads``, in
    percent, and reads nothing where the calls keep no block resident (a
    table in VMEM) or where there is no trace."""
    import importlib.util

    monkeypatch.syspath_prepend(str(CHIP_BENCH))
    spec = importlib.util.spec_from_file_location(
        "resident_share", CHIP_BENCH / "metrics" / "sched_kernel.resident_share.py")
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    ctx = lambda window: types.SimpleNamespace(  # noqa: E731
        trace=types.SimpleNamespace(window=window))

    monkeypatch.setattr(jax_sched, "CHUNK", 128)
    ev = _events(100, 300, 130, seed=13)
    assert reader.read(ctx((0, 1)), tmp_path / "none") is None
    _, spans = _traced(tmp_path / "vmem", lambda: sched_many_fused(
        _state(300, 130), ev, interpret=True))
    assert reader.read(ctx((spans[0][1], spans[-1][2])), tmp_path / "vmem") is None
    _in_hbm(monkeypatch, 300, 130)

    def two_calls():
        first = sched_many_fused(_state(300, 130), ev, interpret=True)
        return sched_many_fused(first[0], ev[:40], interpret=True)

    _, spans = _traced(tmp_path / "hbm", two_calls)
    fused = [s for s in spans if s[0] == "sched.fused"]
    slots = sched_step.hbm_slots(300, 130)
    (l1, r1), (l2, r2) = (_walk(e[:, 0], e[:, 1], slots, 128) for e in (ev, ev[:40]))
    assert 0 < r1 < r1 + l1
    assert reader.read(ctx((fused[0][1], fused[1][2])), tmp_path / "hbm") == pytest.approx(
        100.0 * (r1 + r2) / (r1 + l1 + r2 + l2))
    assert reader.read(ctx((fused[1][1], fused[1][2])), tmp_path / "hbm") == pytest.approx(
        100.0 * r2 / (r2 + l2))
