"""The profiler spans and the compile count inside ``sched_many_fused``.

The kernel path of ``core.jax_sched.sched_many_fused`` writes
``jax.profiler.TraceAnnotation`` spans into the profiler's trace, on the
same clock as the device's operations: ``sched.fused`` per call, with its
``chunks``, ``events``, ``padded`` and ``compiles`` as metadata, and inside
it one ``sched.stage_in``, one ``sched.launch`` (the call's one jitted
program, whatever its number of chunks) and one ``sched.stage_out``.
These tests trace calls on the CPU, with the kernel in Pallas's
interpreter, and read the trace back with ``jax.profiler.ProfileData``.
"""

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.jax_sched import ARRIVAL, EVICT, FINISH, JIQState, sched_many_fused
from repro.utils.compile_cache import compile_count

STAGES = ("sched.stage_in", "sched.launch", "sched.stage_out")


def _events(n, n_funcs, n_workers, seed):
    """A mixed stream whose FINISH/EVICT notices name any worker: the
    scheduler's answers need not be meaningful, only equal run to run."""
    rng = np.random.default_rng(seed)
    kinds = rng.choice([ARRIVAL, FINISH, EVICT], size=n, p=[0.5, 0.4, 0.1])
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
    _, spans = _traced(tmp_path, lambda: sched_many_fused(
        state, ev, chunk=1024, interpret=True))
    fused = [s for s in spans if s[0] == "sched.fused"]
    assert len(fused) == 1
    _, lo, hi, stats = fused[0]
    assert {k: stats[k] for k in ("chunks", "events", "padded")} == {
        "chunks": 3, "events": 2500, "padded": 3 * 1024 - 2500}
    names = [s[0] for s in spans if s[0] != "sched.fused"]
    assert names == list(STAGES)
    assert all(lo <= s[1] <= s[2] <= hi for s in spans)


def test_a_stream_length_compiles_one_program_once():
    """A first call at a length (and table shape) no other test uses
    compiles the call's one program, kernel and all; the same length again
    compiles nothing, whatever the events."""
    count = compile_count()
    state = jax.block_until_ready(_state(7, 13))
    made = []
    for seed in (4, 5, 6):
        before = count.compiles
        jax.block_until_ready(sched_many_fused(
            state, _events(77, 7, 13, seed), chunk=32, interpret=True))
        made.append(count.compiles - before)
    assert made == [1, 0, 0]


def test_compiles_are_counted_in_the_call_that_makes_them(tmp_path):
    """A first call at a table shape no other test uses compiles; the same
    call again compiles nothing."""
    state, ev = _state(3, 17), _events(300, 3, 17, seed=2)

    def twice():
        first = sched_many_fused(state, ev, chunk=256, interpret=True)
        jax.block_until_ready(first)
        return sched_many_fused(state, ev, chunk=256, interpret=True)

    _, spans = _traced(tmp_path, twice)
    first, again = [s[3] for s in spans if s[0] == "sched.fused"]
    assert first["compiles"] > 0
    assert again["compiles"] == 0
    assert first["padded"] == again["padded"] == 2 * 256 - 300


def test_answers_and_state_are_the_same_with_a_trace_active(tmp_path):
    state, ev = _state(4, 9), _events(700, 4, 9, seed=3)
    call = lambda: sched_many_fused(state, ev, chunk=512, interpret=True)  # noqa: E731
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
