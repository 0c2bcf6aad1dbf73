"""Benchmark runner — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (per repo convention).
``--quick`` shrinks the simulation matrix for CI.  Full results are also
persisted as JSON under ``--results-dir`` (default: benchmarks/results/local,
which is gitignored — the checked-in baselines under benchmarks/results/ are
only rewritten when you pass that directory explicitly; see
docs/BENCHMARKS.md).
"""

from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None, help="comma list of bench names")
    ap.add_argument(
        "--results-dir",
        default=None,
        help="where bench modules persist their JSON results (default: "
        "benchmarks/results/local, so default runs never churn the "
        "checked-in baselines; pass benchmarks/results to refresh them)",
    )
    args = ap.parse_args(argv)

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (
        bench_admission,
        bench_affinity,
        bench_autoscale,
        bench_chaos,
        bench_coldstart,
        bench_concurrency,
        bench_imbalance,
        bench_kernels,
        bench_latency,
        bench_overhead,
        bench_policies,
        bench_pull_dispatch,
        bench_shard_scale,
        bench_sim_speed,
        bench_stealing,
        bench_table1,
        bench_trace,
        bench_throughput,
        common,
    )

    if args.results_dir:
        common.set_results_dir(args.results_dir)

    modules = {
        "table1": bench_table1,
        "trace": bench_trace,
        "latency": bench_latency,
        "coldstart": bench_coldstart,
        "imbalance": bench_imbalance,
        "throughput": bench_throughput,
        "concurrency": bench_concurrency,
        "overhead": bench_overhead,
        "kernels": bench_kernels,
        "pull_dispatch": bench_pull_dispatch,
        "sim_speed": bench_sim_speed,
        "shard_scale": bench_shard_scale,
        "admission": bench_admission,
        "affinity": bench_affinity,
        "stealing": bench_stealing,
        "policies": bench_policies,
        "chaos": bench_chaos,
        "autoscale": bench_autoscale,
    }
    if args.only:
        keep = set(args.only.split(","))
        modules = {k: v for k, v in modules.items() if k in keep}

    print("name,us_per_call,derived")
    for name, mod in modules.items():
        t0 = time.time()
        try:
            rows = mod.run(quick=args.quick)
        except Exception as e:  # keep the harness running; surface the error
            print(f"{name}/ERROR,0,{type(e).__name__}: {e}", flush=True)
            continue
        for rname, us, derived in rows:
            print(f"{rname},{us:.3f},{str(derived).replace(',', ';')}", flush=True)
        print(f"_bench_wall/{name},{(time.time()-t0)*1e6:.0f},seconds={time.time()-t0:.1f}",
              flush=True)


if __name__ == "__main__":
    main()
