"""Tests of the readers of the program's own spans (``program_spans.py``).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

The recorded trace ``data/program_trace`` is the whole traced window of a
0.3 s ``--trace 1`` run of ``hiku5w-batch8`` on a TPU v5 lite, cut to the
device's ``XLA Ops`` line and the ``dispatch.*`` and ``sched.*`` host spans;
its ``expected.json`` holds the five values worked out by plain loops, and
the harness's own count of the window's real events.  ``data/trace`` is the
earlier recording, of a program that writes no ``sched.*`` span.
"""

from __future__ import annotations

import functools
import json
import sys
import time
import types
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
DATA = CHIP / "tests" / "data"
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(ROOT / "src"))

import harness  # noqa: E402
import program_spans  # noqa: E402
from trace_reduce import reduce_trace  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NEW = ("sched.stage_in_us_per_call", "sched.launch_us_per_call",
       "sched.stage_out_us_per_call", "sched_kernel.pad_share", "sched.window_compiles")
RECORDED = DATA / "program_trace"
WANT = json.loads((RECORDED / "expected.json").read_text())


def _reader(name):
    return harness.load_module(CHIP / "metrics" / f"{name}.py").read


def _ctx(trace_dir):
    spans = tuple(json.loads((trace_dir / "expected.json").read_text())["spans"])
    return types.SimpleNamespace(trace=reduce_trace(trace_dir / "trace.xplane.pb", spans))


def test_the_five_metrics_are_appended_entries():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"][-5:]] == list(NEW)
    wrapper = entries["dispatch.host_us_per_call"]["layer"]
    kernel = entries["sched_kernel.ns_per_event"]["layer"]
    for name in NEW:
        m = entries[name]
        assert m["source"] == "device_trace" and "workloads" not in m
        assert m["layer"] == (kernel if name == "sched_kernel.pad_share" else wrapper)
        assert (CHIP / "metrics" / f"{name}.py").is_file()


@pytest.mark.parametrize("name", NEW)
def test_each_reader_on_the_recorded_program_trace(name, monkeypatch):
    monkeypatch.setattr(program_spans, "TRACE_DIR", RECORDED)
    got = _reader(name)(_ctx(RECORDED))
    assert got == pytest.approx(WANT["metrics"][name], rel=1e-12)


@pytest.mark.parametrize("name", NEW)
def test_each_reader_reads_none_without_program_spans(name, monkeypatch, tmp_path):
    """On the earlier recording, whose program wrote no ``sched.*`` span,
    and where there is no trace at all."""
    ctx = _ctx(DATA / "trace")
    monkeypatch.setattr(program_spans, "TRACE_DIR", DATA / "trace")
    assert _reader(name)(ctx) is None
    monkeypatch.setattr(program_spans, "TRACE_DIR", tmp_path / "none")
    assert _reader(name)(ctx) is None


def test_a_stale_trace_outside_the_window_reads_none():
    ctx = _ctx(RECORDED)
    lo, hi = ctx.trace.window
    ctx.trace.window = (hi + 1.0, hi + 2.0)
    assert program_spans.calls(ctx, RECORDED) is None


def test_recorded_calls_match_the_benchmarks_own_count():
    """One ``sched.fused`` inside each ``dispatch.sched_many_fused`` span, on
    the one clock of the trace, and the real events they name are the ones
    the harness counted for the window."""
    ctx = _ctx(RECORDED)
    calls = program_spans.calls(ctx, RECORDED)
    ds, de = ctx.trace.spans["dispatch.sched_many_fused"]
    assert len(calls) == len(ds) == WANT["calls"]
    o = np.argsort(ds)
    ds, de = ds[o], de[o]
    assert np.all((ds <= calls.starts) & (calls.ends <= de))
    assert int(calls.stats["events"].sum()) == WANT["real_events"]
    assert int(calls.stats["padded"].sum()) == WANT["padded"]
    assert np.all(calls.stats["chunks"] == 1)
    for stage in program_spans.STAGES:
        n = 2 if stage == "sched.stage_out" else 1
        assert len(calls.stages[stage][0]) == n * len(calls)


def test_a_traced_run_reports_the_five_metrics(tmp_path, monkeypatch):
    """The harness's traced run on the CPU, the kernel interpreted: every
    new reader reads a number from the program's spans in the window."""
    from repro.core.jax_sched import sched_many_fused

    config = json.loads((CHIP / "configs" / "hiku5w-50vu.json").read_text())
    traffic = dict(json.loads((CHIP / "traffic" / "batch8.json").read_text()),
                   calls_per_segment=12)
    per = [m for m in BENCH["per_layer"] if m["name"] in NEW]
    cell = harness.Cell("tiny", 1, config, traffic,
                        harness.load_module(CHIP / "systems" / "pull_dispatch.py"),
                        BENCH["end_to_end"], per, {m["name"]: _reader(m["name"]) for m in per})
    monkeypatch.setattr(program_spans, "TRACE_DIR", tmp_path / "trace")
    out = harness.run_cell(cell, 2**31 + 12345, 0.3, True, time.perf_counter(),
                           tmp_path / "trace",
                           sut=functools.partial(sched_many_fused, interpret=True))
    assert out["correct"]
    metrics = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(metrics) == set(NEW)
    assert metrics["sched.window_compiles"] == 0
    assert 90.0 < metrics["sched_kernel.pad_share"] < 100.0
    assert all(metrics[n] > 0 for n in NEW[:3])
