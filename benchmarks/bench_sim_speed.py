"""Simulator engine throughput vs the checked-in seed baseline (PR 1).

Measures end-to-end ``Simulator.run`` events/sec (records/sec and processed
heap-events/sec) for the paper-scale protocol and for production-scale
clusters, and compares against ``results/sim_speed_baseline.json`` — a
measurement of the pre-refactor (seed) engine checked in alongside the
refactor.  Because the refactored engine replays byte-identical
``RequestRecord`` streams (tests/test_equivalence.py), the records/sec ratio
*is* the event-throughput speedup.

Also reports the §V benchmark-matrix wall time (the workload every figure
module replays).  All of it is host-CPU time: the engine never touches the
device.

Caches (shared VU programs / fluctuation bands) are cleared before each
repeat so the numbers measure the engine, not warm caches; the baseline was
measured the same way.
"""

from __future__ import annotations

import gc
import json
import time
from pathlib import Path

BASELINE = Path(__file__).parent / "results" / "sim_speed_baseline.json"

# configs must mirror the baseline file entries
CONFIGS = {
    "paper_5w_50vu": dict(n_workers=5, n_vus=50, duration_s=60.0),
    "scale_100w_1000vu": dict(n_workers=100, n_vus=1000, duration_s=15.0),
    "scale_400w_4000vu": dict(n_workers=400, n_vus=4000, duration_s=10.0),
    "scale_800w_8000vu_8g": dict(
        n_workers=800, n_vus=8000, duration_s=10.0, mem_pool_mb=8192.0
    ),
    "scale_1600w_16000vu_8g": dict(
        n_workers=1600, n_vus=16000, duration_s=6.0, mem_pool_mb=8192.0
    ),
}
QUICK_CONFIGS = ("paper_5w_50vu", "scale_400w_4000vu")


def _clear_engine_caches() -> None:
    from repro.core import simulator as _sim
    from repro.core import trace as _trace

    _sim._FLUCT_CACHE.clear()
    _trace._PROG_CACHE.clear()


def _run_once(cfg_kw: dict):
    from repro.core import SimConfig, Simulator, make_scheduler

    kw = dict(cfg_kw)
    n_vus = kw.pop("n_vus")
    duration_s = kw.pop("duration_s")
    sched = make_scheduler("hiku", kw["n_workers"], seed=0)
    sim = Simulator(sched, cfg=SimConfig(**kw), seed=0)
    t0 = time.perf_counter()
    recs = sim.run(n_vus=n_vus, duration_s=duration_s)
    wall = time.perf_counter() - t0
    return len(recs), sim.n_events, wall


def run(quick: bool = False):
    rows = []
    baseline = json.loads(BASELINE.read_text())["configs"] if BASELINE.exists() else {}
    names = QUICK_CONFIGS if quick else list(CONFIGS)
    repeats = 1 if quick else 2
    for name in names:
        best = None
        for _ in range(repeats):
            _clear_engine_caches()
            gc.collect()
            n_rec, n_ev, wall = _run_once(CONFIGS[name])
            if best is None or wall < best[2]:
                best = (n_rec, n_ev, wall)
        n_rec, n_ev, wall = best
        rec_s = n_rec / wall
        ev_s = n_ev / wall
        base = baseline.get(name, {}).get("records_per_s")
        speedup = rec_s / base if base else float("nan")
        rows.append(
            (
                f"sim_speed/{name}",
                wall / n_ev * 1e6,  # us per processed event
                f"records_per_s={rec_s:.0f};events_per_s={ev_s:.0f};"
                f"seed_records_per_s={base};speedup={speedup:.1f}x",
            )
        )
    # §V experiment matrix wall time (what every figure module replays)
    from . import common

    _clear_engine_caches()
    gc.collect()
    t0 = time.perf_counter()
    m = common.run_matrix(quick=True)
    matrix_wall = time.perf_counter() - t0
    n_req = sum(m[s]["n_requests"] for s in m)
    rows.append(
        (
            "sim_speed/matrix_quick",
            matrix_wall / max(n_req, 1) * 1e6,
            f"wall_s={matrix_wall:.2f};requests={n_req}",
        )
    )
    return rows


if __name__ == "__main__":
    for r in run(quick=True):
        print(r)
