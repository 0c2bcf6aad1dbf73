"""Sharded multi-cluster driver throughput at the production-scale anchors.

For each anchor (800 and 1600 total workers, 8 GB pools — the same configs
``bench_sim_speed`` tracks) runs the sharded driver at 1, 4, and 8 shards
and reports two rates:

* ``makespan_ev_s`` — total events / end-to-end wall time of the driver
  (process-pool backend for K>1; bounded by the local core count, so on a
  2-core CI box this tops out near 2x);
* ``aggregate_ev_s`` — the scale-out capacity metric: the sum of per-shard
  event rates, each shard timed on its own wall clock inside its worker
  process, exactly what K independent single-cluster deployments would
  report in aggregate.  ``speedup_vs_1shard`` is computed on this metric
  (the 1-shard case is the monolithic engine run through the same driver).

The **mega anchor** (``--mega``) is the 100k-worker / 1M-VU cluster at 8,
16, 32 and 64 shards.  Two acceptance rows ride along:

* ``flat_curve`` — aggregate events/sec must not drop more than 10% from
  8 to 64 shards (per-shard cost must not grow with cluster size);
* ``vs_legacy_8shards`` — the refactored control plane (bitmap
  least-connections tracker, vectorized VU-program generation, shared-
  memory shard transport) must deliver >=2x the aggregate events/sec of
  the legacy engine path (full-scan fallback, per-VU program loop,
  pickled results) on the identical workload.  Both sides of this ratio
  run their shards serially in the benchmark's own process: the legacy
  path is patched in as class and module attributes, which a spawned
  pool child would not see (the pool spawns once a JAX backend is live,
  as after ``table1`` in ``benchmarks.run``).

``--quick`` runs the 2-shard smoke plus a reduced-scale replica of the
mega curve + acceptance rows (CI path; looser thresholds since sub-second
shards are noisy).
"""

from __future__ import annotations

import contextlib
import gc
import os

ANCHORS = {
    "800w_8000vu_8g": dict(n_workers=800, n_vus=8000, duration_s=4.0, mem_pool_mb=8192.0),
    "1600w_16000vu_8g": dict(n_workers=1600, n_vus=16000, duration_s=3.0, mem_pool_mb=8192.0),
}
SHARD_COUNTS = (1, 4, 8)
QUICK_SMOKE = dict(n_workers=200, n_vus=2000, duration_s=2.0, mem_pool_mb=2048.0)

MEGA_ANCHOR = dict(
    n_workers=100_000, n_vus=1_000_000, duration_s=1.0, mem_pool_mb=8192.0
)
MEGA_SHARD_COUNTS = (8, 16, 32, 64)
MEGA_MAX_DROP = 0.10  # acceptance: <=10% aggregate drop, 8 -> 64 shards
MEGA_MIN_LEGACY_RATIO = 2.0  # acceptance: >=2x over the legacy engine path

MEGA_QUICK = dict(n_workers=2_000, n_vus=20_000, duration_s=1.0, mem_pool_mb=4096.0)
MEGA_QUICK_SHARD_COUNTS = (2, 8)
MEGA_QUICK_MAX_DROP = 0.35  # sub-second shards: wide noise band
MEGA_QUICK_MIN_LEGACY_RATIO = 1.0  # sanity (gains shrink with shard size)


def _clear_engine_caches() -> None:
    from repro.core import simulator as _sim
    from repro.core import trace as _trace

    _sim._FLUCT_CACHE.clear()
    _trace._PROG_CACHE.clear()


@contextlib.contextmanager
def _legacy_engine():
    """Run the driver on the pre-refactor control plane: full-scan
    least-connections fallback, per-VU program generation, pickled shard
    results.  The class/module attributes patched here hold only in this
    process, so run the driver with ``backend="serial"`` under it."""
    from repro.core import shard, trace
    from repro.core.scheduler import Scheduler

    saved_lc = Scheduler._least_connections
    saved_fast = trace._PROG_FAST_OK
    saved_env = os.environ.get(shard.TRANSPORT_ENV)
    Scheduler._least_connections = Scheduler._least_connections_ref
    trace._PROG_FAST_OK = False
    os.environ[shard.TRANSPORT_ENV] = "pickle"
    try:
        yield
    finally:
        Scheduler._least_connections = saved_lc
        trace._PROG_FAST_OK = saved_fast
        if saved_env is None:
            os.environ.pop(shard.TRANSPORT_ENV, None)
        else:
            os.environ[shard.TRANSPORT_ENV] = saved_env


def _run(n_shards: int, cfg_kw: dict, backend: str):
    from repro.core import SimConfig
    from repro.core.shard import ShardedSimulator

    kw = dict(cfg_kw)
    n_vus = kw.pop("n_vus")
    duration_s = kw.pop("duration_s")
    n_workers = kw.pop("n_workers")
    _clear_engine_caches()
    gc.collect()
    driver = ShardedSimulator(
        n_shards, n_workers, scheduler="hiku", cfg=SimConfig(**kw), seed=0, backend=backend
    )
    return driver.run(n_vus=n_vus, duration_s=duration_s)


def _mega_rows(anchor_name: str, cfg_kw: dict, shard_counts, max_drop, min_ratio):
    """Events/sec-vs-cluster-size curve + the two acceptance rows."""
    rows, curve = [], {}
    for k in shard_counts:
        r = _run(k, cfg_kw, backend="process")
        curve[k] = r.aggregate_events_per_s
        rows.append(
            (
                f"shard_scale/{anchor_name}/{k}shards",
                r.wall_s / max(r.n_events, 1) * 1e6,
                f"events={r.n_events};makespan_s={r.wall_s:.2f};"
                f"makespan_ev_s={r.events_per_s:.0f};"
                f"aggregate_ev_s={curve[k]:.0f}",
            )
        )
    k_lo, k_hi = shard_counts[0], shard_counts[-1]
    drop = (curve[k_lo] - curve[k_hi]) / curve[k_lo]
    rows.append(
        (
            f"shard_scale/{anchor_name}/flat_curve",
            0.0,
            f"drop_{k_lo}to{k_hi}shards={drop * 100:.1f}%;"
            f"max_allowed={max_drop * 100:.0f}%;"
            f"accept={'PASS' if drop <= max_drop else 'FAIL'}",
        )
    )
    refactored_agg = _run(k_lo, cfg_kw, backend="serial").aggregate_events_per_s
    with _legacy_engine():
        rl = _run(k_lo, cfg_kw, backend="serial")
    legacy_agg = rl.aggregate_events_per_s
    ratio = refactored_agg / legacy_agg if legacy_agg else float("inf")
    rows.append(
        (
            f"shard_scale/{anchor_name}/vs_legacy_{k_lo}shards",
            0.0,
            f"serial_aggregate_ev_s={refactored_agg:.0f};"
            f"legacy_aggregate_ev_s={legacy_agg:.0f};ratio={ratio:.2f}x;"
            f"min_required={min_ratio:.1f}x;"
            f"accept={'PASS' if ratio >= min_ratio else 'FAIL'}",
        )
    )
    payload = {
        "anchor": anchor_name,
        "config": dict(cfg_kw),
        "aggregate_ev_s": {str(k): curve[k] for k in shard_counts},
        "drop_lo_to_hi": drop,
        "max_allowed_drop": max_drop,
        "serial_aggregate_ev_s": refactored_agg,
        "legacy_aggregate_ev_s": legacy_agg,
        "ratio_vs_legacy": ratio,
        "min_required_ratio": min_ratio,
    }
    return rows, payload


def run(quick: bool = False, mega: bool = False):
    rows = []
    if quick:
        r = _run(2, QUICK_SMOKE, backend="auto")
        rows.append(
            (
                "shard_scale/quick_2shards_200w",
                r.wall_s / max(r.n_events, 1) * 1e6,
                f"events={r.n_events};records={len(r.records)};"
                f"makespan_s={r.wall_s:.2f};aggregate_ev_s={r.aggregate_events_per_s:.0f}",
            )
        )
        mega_rows, _ = _mega_rows(
            "mega_quick",
            MEGA_QUICK,
            MEGA_QUICK_SHARD_COUNTS,
            MEGA_QUICK_MAX_DROP,
            MEGA_QUICK_MIN_LEGACY_RATIO,
        )
        rows.extend(mega_rows)
        return rows
    if mega:
        from .common import save_json

        mega_rows, payload = _mega_rows(
            "mega_100kw_1mvu",
            MEGA_ANCHOR,
            MEGA_SHARD_COUNTS,
            MEGA_MAX_DROP,
            MEGA_MIN_LEGACY_RATIO,
        )
        rows.extend(mega_rows)
        save_json("shard_scale_mega", payload)
        return rows
    for aname, cfg_kw in ANCHORS.items():
        base_aggregate = None
        for k in SHARD_COUNTS:
            backend = "serial" if k == 1 else "process"
            r = _run(k, cfg_kw, backend)
            aggregate = r.aggregate_events_per_s
            makespan_rate = r.events_per_s
            if k == 1:
                base_aggregate = aggregate
            speedup = aggregate / base_aggregate if base_aggregate else float("nan")
            rows.append(
                (
                    f"shard_scale/{aname}/{k}shards",
                    r.wall_s / max(r.n_events, 1) * 1e6,
                    f"events={r.n_events};makespan_s={r.wall_s:.2f};"
                    f"makespan_ev_s={makespan_rate:.0f};aggregate_ev_s={aggregate:.0f};"
                    f"speedup_vs_1shard={speedup:.1f}x",
                )
            )
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true", help="CI smoke path")
    ap.add_argument(
        "--mega", action="store_true", help="full 100k-worker/1M-VU anchor (minutes)"
    )
    ap.add_argument(
        "--results-dir",
        default=None,
        help="where save_json writes (default: benchmarks/results/local, gitignored)",
    )
    a = ap.parse_args()
    if a.results_dir:
        from benchmarks import common

        common.set_results_dir(a.results_dir)
    for row in run(quick=a.quick, mega=a.mega):
        print(row)
