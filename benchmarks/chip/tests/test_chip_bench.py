"""Tests of the chip benchmark itself, on the CPU at tiny widths.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

The Pallas kernel runs in its interpreter here; nothing in this file needs
or touches a TPU.
"""

from __future__ import annotations

import functools
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
DATA = CHIP / "tests" / "data"
sys.path.insert(0, str(CHIP))
sys.path.insert(0, str(ROOT / "src"))

import control  # noqa: E402
import harness  # noqa: E402
from deployment import catalogue, make_segment  # noqa: E402
from reference import ARRIVAL, EVICT, FINISH, PullDispatch, brute_force, state_after  # noqa: E402
from trace_reduce import TraceSummary, reduce_trace  # noqa: E402

BIG_SEED = 2**31 + 12345
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


CONFIG = "hiku5w-50vu"


def _config():
    return json.loads((CHIP / "configs" / f"{CONFIG}.json").read_text())


def _tiny_cell(calls=12, traffic="per_arrival"):
    config = _config()
    tr = dict(json.loads((CHIP / "traffic" / f"{traffic}.json").read_text()),
              calls_per_segment=calls)
    system = harness.load_module(CHIP / "systems" / "pull_dispatch.py")
    per = BENCH["per_layer"]
    readers = {m["name"]: harness.load_module(CHIP / "metrics" / f"{m['name']}.py").read
               for m in per}
    return harness.Cell("tiny", 1, config, tr, system, BENCH["end_to_end"], per, readers)


def _interpret_sut():
    from repro.core.jax_sched import sched_many_fused

    return functools.partial(sched_many_fused, interpret=True)


def _run(cell, sut, seconds=0.3, trace=False, tmp=None):
    return harness.run_cell(cell, BIG_SEED, seconds, trace, time.perf_counter(),
                            Path(tmp or "/nonexistent"), sut=sut)


# ------------------------------------------------------------------ layout
@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves_its_files_by_name(workload):
    cell = harness.resolve(ROOT, workload)
    assert hasattr(cell.system, "Run") and callable(cell.system.default_sut)
    assert {"arrivals_per_call", "calls_per_segment"} <= set(cell.traffic)
    assert "setup_s" in {m["name"] for m in cell.end_to_end}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    assert all(callable(r) for r in cell.readers.values())
    entry = {c["name"]: c for c in BENCH["configs"]}[
        {w["name"]: w for w in BENCH["workloads"]}[workload]["config"]]
    assert cell.config["reduced"] == entry["reduced"]
    assert cell.config["name"] == entry["name"]


def test_a_new_config_mix_and_metric_need_only_new_files(tmp_path):
    tree = tmp_path / "benchmarks" / "chip"
    shutil.copytree(CHIP, tree, ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in tree.rglob("*") if p.is_file()}
    bench = json.loads(json.dumps(BENCH))
    config = _config()
    config.update(name="hiku5w-20vu", vus=20)
    (tree / "configs" / "hiku5w-20vu.json").write_text(json.dumps(config))
    (tree / "traffic" / "batch3.json").write_text(json.dumps(
        {"arrivals_per_call": 3, "calls_per_segment": 10}))
    (tree / "metrics" / "dispatch.calls.py").write_text(
        "def read(ctx):\n    return ctx.n_calls\n")
    bench["configs"].append({"name": "hiku5w-20vu", "source": "x",
                             "file": "benchmarks/chip/configs/hiku5w-20vu.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "hiku5w20-batch3", "config": "hiku5w-20vu",
                               "traffic": "batch3", "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "dispatch.calls", "unit": "calls",
                               "better": "higher", "source": "program_span",
                               "layer": "host", "moves": "decisions_per_s",
                               "workloads": ["hiku5w20-batch3"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    copy = harness.load_module(tree / "harness.py", "chipbench_harness_copy")
    cell = copy.resolve(tmp_path, "hiku5w20-batch3")
    assert cell.config["vus"] == 20 and cell.traffic["arrivals_per_call"] == 3
    assert cell.readers["dispatch.calls"](type("C", (), {"n_calls": 7})) == 7
    assert "dispatch.calls" not in {m["name"] for m in copy.resolve(
        tmp_path, "hiku5w-per-arrival").per_layer}
    assert all(p.read_bytes() == b for p, b in before.items())


def test_peaks_table_refuses_an_unknown_device_kind():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v99")


def test_benchmark_json_names_its_cells_and_metrics():
    assert [w["name"] for w in BENCH["workloads"]] == [
        "hiku5w-per-arrival", "hiku5w-batch8"]
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert {w["chips"] for w in BENCH["workloads"]} == {1}
    assert [m["name"] for m in BENCH["end_to_end"]] == [
        "decisions_per_s", "decision_ms_p95", "setup_s"]
    moves = {m["name"]: m["moves"] for m in BENCH["per_layer"]}
    assert moves == {"sched_kernel.ns_per_event": "decisions_per_s",
                     "sched_kernel_roofline": "decisions_per_s",
                     "dispatch.host_us_per_call": "decision_ms_p95",
                     "device.idle_share": "decision_ms_p95"}
    for m in BENCH["per_layer"]:
        assert (CHIP / "metrics" / f"{m['name']}.py").is_file()


# ------------------------------------------------------------------ traffic
def _traffic(name, **kw):
    return dict(json.loads((CHIP / "traffic" / f"{name}.json").read_text()), **kw)


def test_segment_is_deterministic_per_seed():
    cfg, tr = _config(), _traffic("per_arrival", calls_per_segment=200)
    a, b = make_segment(cfg, tr, BIG_SEED), make_segment(cfg, tr, BIG_SEED)
    for name in ("idle", "conns", "events", "bounds", "assign", "warm"):
        assert np.array_equal(getattr(a, name), getattr(b, name)), name
    c = make_segment(cfg, tr, BIG_SEED + 1)
    assert not np.array_equal(a.events, c.events)
    assert a.n_calls == c.n_calls == 200


@pytest.mark.parametrize("traffic", ["per_arrival", "batch8"])
def test_every_call_closes_at_its_last_decision(traffic):
    """Each call of every seed carries the mix's number of ARRIVALs, the
    last of them last: every seed hands over the same decisions per call."""
    cfg, tr = _config(), _traffic(traffic, calls_per_segment=50)
    for seed in (BIG_SEED, 7, 2**33 + 5):
        seg = make_segment(cfg, tr, seed)
        for b in range(seg.n_calls):
            kinds = seg.burst(b)[:, 0]
            assert np.sum(kinds == ARRIVAL) == tr["arrivals_per_call"]
            assert kinds[-1] == ARRIVAL


def test_segment_is_causally_consistent():
    """A FINISH names a worker that holds a connection given before its
    burst (a dispatcher learns an assignment only when its call returns);
    an EVICT names an idle instance, and EVICTs occur; the start state is
    the steady load (running tasks at most one apart across workers)."""
    cfg, tr = _config(), _traffic("batch8", calls_per_segment=100)
    seg = make_segment(cfg, tr, BIG_SEED)
    assert seg.conns.max() - seg.conns.min() <= 1
    assert seg.stats["start_running"] == seg.conns.sum() > 0
    idle, conns = seg.idle.astype(np.int64), seg.conns.astype(np.int64)
    for b in range(seg.n_calls):
        lo = seg.bounds[b]
        given = np.zeros_like(conns)  # connections given in this burst
        for i, (k, f, w) in enumerate(seg.burst(b).tolist()):
            a, warm = seg.assign[lo + i], seg.warm[lo + i]
            if k == ARRIVAL:
                assert w == -1 and 0 <= a < cfg["workers"]
                if warm:
                    assert idle[f, a] > 0
                    idle[f, a] -= 1
                given[a] += 1
            elif k == FINISH:
                assert conns[w] > 0
                idle[f, w] += 1
                conns[w] -= 1
            else:
                assert k == EVICT and idle[f, w] > 0
                idle[f, w] -= 1
        conns += given
    st = seg.stats
    assert st["arrivals"] > 0 and st["finishes"] > 0 and st["warm"] > 0
    assert st["evicts"] == st["pool_evicts"] + st["keepalive_evicts"]
    assert st["pool_evicts"] >= 10 and st["warm"] < st["arrivals"]


def test_start_state_fits_the_pools():
    """Idle instances sit only where the pool holds them beside the tasks
    running there."""
    cfg = _config()
    _, _, _, mem = catalogue(cfg)
    for seed in (BIG_SEED, 3, 2**33 + 1):
        seg = make_segment(cfg, _traffic("per_arrival", calls_per_segment=2), seed)
        assert np.all(mem @ seg.idle <= cfg["pool_mb"])


def test_reference_agrees_with_brute_force():
    for tr in (_traffic("per_arrival", calls_per_segment=300),
               _traffic("batch8", calls_per_segment=40)):
        seg = make_segment(_config(), tr, BIG_SEED)
        a, w, idle, conns = brute_force(seg.idle, seg.conns, seg.events)
        assert np.array_equal(a, seg.assign) and np.array_equal(w, seg.warm)
        ref = PullDispatch(seg.idle, seg.conns)
        ref.run(seg.events)
        got = ref.state()
        assert np.array_equal(got[0], idle) and np.array_equal(got[1], conns)
        want = state_after(seg.idle, seg.conns, seg.events, seg.assign, seg.warm)
        assert np.array_equal(want[0], idle) and np.array_equal(want[1], conns)


def test_control_breaks_the_least_loaded_pull():
    seg = make_segment(_config(), _traffic("per_arrival", calls_per_segment=300), BIG_SEED)
    a, _ = PullDispatch(seg.idle, seg.conns, pull_by_load=False).run(seg.events)
    assert np.sum(a != seg.assign) > 0


def test_reference_matches_the_kernel_in_interpret_mode():
    """At the configuration's own width, over bursts that hold ARRIVALs
    (pulls and fallbacks), FINISHes and EVICTs."""
    from repro.core.jax_sched import JIQState, sched_many_fused
    import jax.numpy as jnp

    seg = make_segment(_config(), _traffic("batch8", calls_per_segment=40), BIG_SEED)
    kinds = seg.events[:, 0]
    last = int(np.searchsorted(seg.bounds, np.flatnonzero(kinds == EVICT)[0], "right"))
    upto = seg.bounds[last]
    assert {ARRIVAL, FINISH, EVICT} <= set(kinds[:upto].tolist())
    assert 0 < seg.warm[:upto].sum() < (kinds[:upto] == ARRIVAL).sum()
    state = JIQState(jnp.asarray(seg.idle), jnp.asarray(seg.conns))
    for b in range(last):
        state, (a, w) = sched_many_fused(state, seg.burst(b), interpret=True)
        lo, hi = seg.bounds[b], seg.bounds[b + 1]
        assert np.array_equal(np.asarray(a), seg.assign[lo:hi])
        assert np.array_equal(np.asarray(w), seg.warm[lo:hi])
    want = state_after(seg.idle, seg.conns, seg.events[:upto], seg.assign[:upto],
                       seg.warm[:upto])
    assert np.array_equal(np.asarray(state.idle), want[0])
    assert np.array_equal(np.asarray(state.conns), want[1])


# ------------------------------------------------------------------ check
def _well_formed(out, trace):
    assert list(out)[-1] == "check"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    assert set(out["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    for c in out["check"].values():
        assert set(c) == {"value", "limit"}
    if trace:
        assert {"busy_s", "window_s"} <= set(out["device"]) and "breakdown" in out
    json.loads(json.dumps(out))


def test_a_run_is_correct_and_its_line_well_formed(tmp_path):
    cell = _tiny_cell()
    out = _run(cell, _interpret_sut())
    _well_formed(out, False)
    assert out["correct"] and out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"decisions_per_s", "decision_ms_p95", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    out = _run(cell, _interpret_sut(), trace=True, tmp=tmp_path / "trace")
    _well_formed(out, True)
    assert out["correct"]


@pytest.mark.parametrize("traffic", ["per_arrival", "batch8"])
def test_the_control_reads_not_correct(traffic):
    out = _run(_tiny_cell(calls=300, traffic=traffic), control.ControlSUT())
    assert not out["correct"] and out["check"]["assign_mismatches"]["value"] > 0


@pytest.mark.parametrize("traffic", ["per_arrival", "batch8"])
@pytest.mark.parametrize("fault", sorted(control.FAULTS))
def test_each_fault_reads_not_correct(fault, traffic):
    out = _run(_tiny_cell(traffic=traffic), control.FAULTS[fault](_interpret_sut()))
    assert not out["correct"]
    assert max(c["value"] for c in out["check"].values()) > 0


def test_run_py_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(CHIP / "run.py"), "--workload", "hiku5w-per-arrival",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "device cpu" in p.stderr


# ------------------------------------------------------------------ trace
def test_interval_arithmetic_of_the_reduction():
    s = TraceSummary(["k", "k", "pad"], np.array([10.0, 15.0, 40.0]),
                     np.array([20.0, 30.0, 50.0]), np.zeros(3, int), 1,
                     {"call": (np.array([0.0, 38.0]), np.array([38.0, 60.0])),
                      "fetch": (np.array([25.0]), np.array([38.0]))}, (0.0, 60.0))
    assert s.busy_ns == 30.0 and s.window_ns == 60.0
    assert s.busy_between([0.0, 38.0], [38.0, 60.0]).tolist() == [20.0, 10.0]
    assert s.busy_between([12.0, 45.0], [14.0, 100.0]).tolist() == [2.0, 5.0]
    assert s.op_ns(lambda n: n == "k") == (25.0, 2)
    g_s, g_e, label = s.idle_gaps()
    assert list(zip(g_s, g_e, label)) == [(0.0, 10.0, "call"), (30.0, 40.0, "fetch"),
                                          (50.0, 60.0, "call")]
    assert s.breakdown()["idle_gaps"][0] == ["call", 20e-9]


RECORDED = DATA / "trace"


def test_reduction_of_the_recorded_chip_trace():
    """The first 12 calls of a traced run at 100,000 workers on a TPU v5 lite
    (device ops and the benchmark's spans), against numbers worked out by
    plain loops when the file was cut (``expected.json``)."""
    from costs import is_sched_kernel

    want = json.loads((RECORDED / "expected.json").read_text())
    s = reduce_trace(RECORDED / "trace.xplane.pb", tuple(want["spans"]))
    assert s.n_devices == 1
    assert s.window_ns == want["window_ns"]
    assert s.busy_ns == pytest.approx(want["busy_ns"], rel=1e-12)
    assert s.op_ns(is_sched_kernel) == tuple(want["kernel"])
    assert {n: len(v[0]) for n, v in s.spans.items()} == want["span_counts"]
    ops = dict(s.breakdown()["device_ops"])
    assert ops["_sched_events.1 custom-call"] == pytest.approx(want["kernel"][0] / 1e9)
