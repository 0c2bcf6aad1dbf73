"""The chip benchmark's harness: one cell, one run, one result line.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* ``configs[].file`` -- the configuration, whose ``system`` key names the
  module that drives the system under test, ``systems/<system>.py``;
* ``workloads[].traffic`` -- the traffic mix, ``traffic/<traffic>.json``;
* ``per_layer[].name`` -- the metric's reader, ``metrics/<name>.py``, a
  function ``read(ctx)`` that returns a number or ``None``.

So a later change adds a configuration, a mix or a metric as new files and
new entries, and edits none.

A system module defines ``class Run(config, traffic, seed, sut)``
with ``reference_s`` (the seconds its construction spent in the plain
reference, which ``setup_s`` leaves out), ``warm_up()``, ``window(seconds, annotate)``, ``end_to_end()``,
``layer_context()``, ``free()`` and ``check()``, and ``default_sut()``,
which imports the system under test from the program.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))


def load_module(path: Path, name: str | None = None):
    """Import a Python file by path (metric files have dots in their names)."""
    spec = importlib.util.spec_from_file_location(name or f"chipbench_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    system: object            # the systems/<system>.py module
    end_to_end: list          # metric entries this cell reports untraced
    per_layer: list           # metric entries this cell reports traced
    readers: dict             # per-layer name -> read(ctx)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(root: Path, workload: str) -> Cell:
    """The cell ``workload`` of ``root/BENCHMARK.json``, its files loaded."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(have {sorted(cells)})")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
    system = load_module(HERE / "systems" / f"{config['system']}.py")
    per_layer = [m for m in bench["per_layer"] if _applies(m, workload)]
    readers = {m["name"]: load_module(HERE / "metrics" / f"{m['name']}.py").read
               for m in per_layer}
    return Cell(workload, w["chips"], config, traffic, system,
                [m for m in bench["end_to_end"] if _applies(m, workload)],
                per_layer, readers)


def peaks_for(kind: str) -> dict:
    """The peak rates of ``kind``; a kind not in ``peaks.json`` is an error."""
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


class CompileMeter:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit is counted as its retrieval time)."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == self._EVENT:
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def _peak_memory(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
             trace_dir: Path, sut=None) -> dict:
    """Set up, measure for ``seconds``, check; returns the result object.
    ``sut`` replaces the system under test (tests and the control)."""
    import jax

    meter = CompileMeter()
    t_gen = time.perf_counter()
    run = cell.system.Run(cell.config, cell.traffic, seed,
                          sut if sut is not None else cell.system.default_sut())
    t_warm = time.perf_counter()
    run.warm_up()
    t_ready = time.perf_counter()
    # the reference's own seconds while the traffic was made are not set-up
    setup_s = t_ready - t_start - run.reference_s
    print(f"setup_s {setup_s}: start {t_gen - t_start} s, traffic and state "
          f"{t_warm - t_gen - run.reference_s} s (reference {run.reference_s} s "
          f"apart), warm-up {t_ready - t_warm} s; {meter.compiles} compiles, "
          f"{meter.seconds} s, {meter.hits} cache hits", file=sys.stderr)
    compiles = meter.compiles
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        run.window(seconds, annotate=trace)
    finally:
        if trace:
            jax.profiler.stop_trace()
    print(f"window: {meter.compiles - compiles} compiles", file=sys.stderr)
    devices = jax.devices()[:cell.chips]
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": _peak_memory(devices)}
    result = {}
    if trace:
        from trace_reduce import reduce_trace

        summary = reduce_trace(trace_dir, run.span_names)
        ctx = run.layer_context(summary)
        ctx.peaks = peaks_for(dev.device_kind) if dev.platform == "tpu" else None
        metrics = {}
        for m in cell.per_layer:
            value = cell.readers[m["name"]](ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        device["busy_s"] = summary.busy_ns / 1e9
        device["window_s"] = summary.window_ns / 1e9
        result["breakdown"] = summary.breakdown()
    else:
        values = dict(run.end_to_end(), setup_s=setup_s)
        metrics = {m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
                   for m in cell.end_to_end}
    run.free()
    check = run.check()
    correct = all(c["value"] <= c["limit"] for c in check["numbers"].values())
    out = {"correct": bool(correct and check["attempted"] > 0),
           "attempted": check["attempted"], "failed": check["failed"],
           "metrics": metrics, "device": device}
    out.update(result)
    out["check"] = check["numbers"]
    return out


def use_checkout(root: Path) -> None:
    """Point this process at the checkout: its ``src`` on the path, and
    JAX's compile cache at a fixed path inside it, whatever the environment
    says, never evicted (JAX's eviction scan fails on an entry whose
    access-time file it has not written yet, and the cache then takes no
    entry).  Call before JAX is imported; the program's helper takes the
    directory from here."""
    import os

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    sys.path.insert(0, str(root / "src"))


def main(argv=None, t_start: float | None = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one cell of BENCHMARK.json once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = HERE.parents[1]
    cell = resolve(root, args.workload)
    use_checkout(root)
    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device {dev.platform} {dev.device_kind} x{len(devices)}", file=sys.stderr)
    if dev.platform != "tpu" or len(devices) < cell.chips:
        print(f"chip benchmark: needs {cell.chips} TPU chip(s), JAX has "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 3
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start,
                      root / ".chipbench" / "trace")
    for name, c in result["check"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
