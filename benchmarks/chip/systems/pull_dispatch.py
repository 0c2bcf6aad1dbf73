"""The pull-dispatch system: drives one ``sched_many_fused`` call per burst.

The system under test is ``repro.core.jax_sched.sched_many_fused`` with its
default chunk: the host wrapper, ``kernels/ops.sched_events`` and the Pallas
kernel ``kernels/sched_step``.  The window is a closed loop with one
dispatcher: each call gets one burst of events as a host array, and its
assignments and warm flags are on the host before the next burst is handed
over (a dispatcher must give each task to its worker before a later FINISH
can name it).  The ``JIQState`` is carried from call to call; the segment
of bursts is replayed from its start state whenever it ends.

End-to-end metrics: ``decisions_per_s``, the ARRIVAL decisions of the
window over its seconds, and ``decision_ms_p95``, the 95th percentile over
every decision of the window of the time from its burst being handed over
to its assignment being on the host.

Correctness: every answer of every call in the window, and the state after
the last call and after the first whole segment, against the plain
reference's (``reference.py``), exactly.
"""

from __future__ import annotations

import sys
import time
import types
from contextlib import nullcontext

import numpy as np

from deployment import make_segment
from reference import ARRIVAL, state_after

SPANS = ("dispatch.call", "dispatch.sched_many_fused", "dispatch.fetch")


def default_sut():
    from repro.core.jax_sched import sched_many_fused

    return sched_many_fused


class Run:
    span_names = SPANS

    def __init__(self, config, traffic, seed, sut):
        import jax.numpy as jnp
        from repro.core.jax_sched import JIQState

        self.config = config
        self.sut = sut
        self.seg = seg = make_segment(config, traffic, seed)
        self.bursts = [seg.burst(b) for b in range(seg.n_calls)]
        self.n_arrivals = np.array([int(np.sum(b[:, 0] == ARRIVAL)) for b in self.bursts])
        self.start = JIQState(jnp.asarray(seg.idle), jnp.asarray(seg.conns))
        self.calls = None
        self.reference_s = seg.stats["reference_s"]
        print(f"traffic: {seg.stats}", file=sys.stderr)

    def _call(self, state, burst):
        import jax

        state, out = self.sut(state, burst)
        return state, jax.device_get(out)

    def warm_up(self):
        """Compile every burst length of the segment once (the wrapper's
        padding and slicing compile per length), the state carried."""
        import jax

        first = {}
        for b, burst in enumerate(self.bursts):
            first.setdefault(len(burst), b)
        state = self.start
        for b in first.values():
            state, _ = self._call(state, self.bursts[b])
        jax.block_until_ready(state)

    def window(self, seconds, annotate=False):
        import jax

        span = jax.profiler.TraceAnnotation if annotate else nullcontext
        sut, bursts, start = self.sut, self.bursts, self.start
        n_bursts = len(bursts)
        ticks, t0s, t1s, outs = [], [], [], []
        first_end = None
        state = start
        i = 0
        t_begin = time.perf_counter_ns()
        deadline = t_begin + int(seconds * 1e9)
        while True:
            b = i % n_bursts
            if b == 0 and i:
                if first_end is None:
                    first_end = state
                state = start
            burst = bursts[b]
            t0 = time.perf_counter_ns()
            with span(SPANS[0]):
                with span(SPANS[1]):
                    state, out = sut(state, burst)
                with span(SPANS[2]):
                    out = jax.device_get(out)
            t1 = time.perf_counter_ns()
            ticks.append(b)
            t0s.append(t0)
            t1s.append(t1)
            outs.append(out)
            i += 1
            if t1 >= deadline:
                break
        self.calls = types.SimpleNamespace(
            tick=np.array(ticks), t0=np.array(t0s), t1=np.array(t1s), outs=outs,
            t_begin=t_begin, t_end=t1s[-1], last_state=state, first_end=first_end)

    def end_to_end(self):
        c = self.calls
        decisions = self.n_arrivals[c.tick]
        latency_ms = (c.t1 - c.t0) / 1e6
        return {
            "decisions_per_s": decisions.sum() / ((c.t_end - c.t_begin) / 1e9),
            "decision_ms_p95": float(np.percentile(np.repeat(latency_ms, decisions), 95)),
        }

    def layer_context(self, summary):
        c = self.calls
        sizes = np.diff(self.seg.bounds)[c.tick]
        return types.SimpleNamespace(
            trace=summary, n_calls=len(c.tick), real_events=int(sizes.sum()),
            events_per_call=sizes, n_funcs=self.seg.idle.shape[0],
            n_workers=self.seg.idle.shape[1], peaks=None)

    def free(self):
        """Bring the states to the host and drop every device array."""
        c = self.calls
        host = [(np.asarray(s[0]), np.asarray(s[1])) if s is not None else None
                for s in (c.last_state, c.first_end)]
        c.last_state, c.first_end = host
        self.start = None

    def check(self):
        seg, c = self.seg, self.calls
        bad_assign = bad_warm = failed = 0
        for b, (a, w) in zip(c.tick.tolist(), c.outs):
            lo, hi = seg.bounds[b], seg.bounds[b + 1]
            want_a, want_w = seg.assign[lo:hi], seg.warm[lo:hi]
            a, w = np.asarray(a).reshape(-1), np.asarray(w).reshape(-1)
            arr = seg.events[lo:hi, 0] == ARRIVAL
            if a.shape != want_a.shape or w.shape != want_w.shape:
                bad_assign += hi - lo
                bad_warm += hi - lo
                failed += int(arr.sum())
                continue
            da, dw = a != want_a, w.astype(bool) != want_w
            bad_assign += int(da.sum())
            bad_warm += int(dw.sum())
            failed += int(((da | dw) & arr).sum())
        bad_state = 0
        b_last = int(c.tick[-1])
        compared = [(c.last_state, seg.bounds[b_last + 1])]
        if c.first_end is not None:
            compared.append((c.first_end, seg.bounds[-1]))
        for (idle, conns), upto in compared:
            want_idle, want_conns = state_after(
                seg.idle, seg.conns, seg.events[:upto], seg.assign[:upto], seg.warm[:upto])
            bad_state += _differ(idle, want_idle) + _differ(conns, want_conns)
        return {
            "attempted": int(self.n_arrivals[c.tick].sum()),
            "failed": failed,
            "numbers": {
                "assign_mismatches": {"value": bad_assign, "limit": 0},
                "warm_mismatches": {"value": bad_warm, "limit": 0},
                "state_mismatches": {"value": bad_state, "limit": 0},
            },
        }


def _differ(got, want) -> int:
    got = np.asarray(got)
    if got.shape != want.shape:
        return int(want.size)
    return int(np.sum(got != want))
