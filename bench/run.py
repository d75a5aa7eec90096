#!/usr/bin/env python3
"""Run one cell of the chip benchmark once, in this process.

    python3 bench/run.py --workload covtype_otf.fit --seed 7 --seconds 10 --trace 0

The cell, its configuration, traffic mix, limits and metric readers are
found by name (``bench/spec.py``). The run

1. exits non-zero, printing no result, unless JAX finds a TPU with as many
   chips as the cell asks for;
2. sets up the cell from ``--seed``: data and weights on the device, the
   program's objects, and every shape the window uses warmed (``setup_s``,
   from process start to the window);
3. measures for ``--seconds`` seconds (with ``--trace 1`` under the JAX
   profiler, whose trace ``bench/trace.py`` reduces);
4. reads the peak device memory, frees the program's state and compares
   what the window produced with ``bench/reference.py``;
5. prints each compared number beside its limit as the last lines of
   standard error, and one JSON object as the last line of standard output:
   ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
   metrics, or with ``--trace 1`` its per-layer metrics), ``device``,
   ``breakdown`` (traced runs) and ``checks``.

JAX's persistent compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set,
else ``bench/.jax_cache`` in this checkout.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# libtpu logs to /tmp/tpu_logs unless told otherwise: keep it off fixed paths
os.environ.setdefault("TPU_LOG_DIR", "disabled")
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import spec  # noqa: E402


class CompileLog:
    """Host time JAX spends tracing, lowering and compiling (cache loads
    included), and the compiles that missed the persistent cache, with
    the host time each was recorded at."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.durations = []            # (perf_counter, seconds)
        self.compiles = []             # perf_counter of each backend compile
        self.hits = []                 # perf_counter of each cache hit

    def on_duration(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            now = time.perf_counter()
            self.durations.append((now, duration))
            if event == self.EVENTS[2]:
                self.compiles.append(now)

    def on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits.append(time.perf_counter())

    def seconds_between(self, t0: float, t1: float) -> float:
        return sum(d for t, d in self.durations if t0 <= t <= t1)

    def misses_between(self, t0: float, t1: float) -> int:
        return (sum(t0 <= t <= t1 for t in self.compiles)
                - sum(t0 <= t <= t1 for t in self.hits))


_LOG = None


def compile_log() -> CompileLog:
    """The process's one compile log (jax.monitoring listeners cannot be
    taken back, so they are registered once)."""
    global _LOG
    if _LOG is None:
        import jax
        _LOG = CompileLog()
        jax.monitoring.register_event_duration_secs_listener(
            _LOG.on_duration)
        jax.monitoring.register_event_listener(_LOG.on_event)
    return _LOG


def enable_compile_cache() -> str:
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(BENCH / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _device(devices) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, require_tpu: bool = True,
             t0: float = T0) -> int:
    """One run of one cell; prints the result line. Returns the exit code."""
    import jax

    from bench import drivers, peaks
    from bench import trace as tracemod

    cell = spec.load_cell(workload, root)
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        print(f"bench: {workload} needs {cell.chips} TPU chip(s); JAX sees "
              f"{devs}. Nothing was run.", file=sys.stderr)
        return 2
    devices = devs[:cell.chips]
    log = compile_log()
    driver = drivers.make(cell, seed, seconds, devices, span)
    driver.setup()
    rec = {"cell": cell.name, "chips": cell.chips, "config": cell.config,
           "traffic": cell.traffic, "compile_log": log, "trace": None,
           "peak": peaks.peak(devices[0].device_kind) if require_tpu
           else None}
    rec["setup_s"] = time.perf_counter() - t0
    trace_dir = Path(root) / "bench" / ".runs" / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # the harness's spans suffice
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    w0 = time.perf_counter()
    with span("bench.window"):
        driver.window(seconds, rec)
    w1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
        with open(tracemod.newest(str(trace_dir)), "rb") as f:
            rec["trace"] = tracemod.reduce(tracemod.read(f.read()))
    rec["window"] = {"start": w0, "end": w1,
                     "compile_misses": log.misses_between(w0, w1)}
    device = _device(devices)
    if trace:
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
    driver.release()
    numbers = driver.check()
    checks = {name: {"value": float(v), "limit": float(cell.limits[name])}
              for name, v in numbers.items()}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = m.read(rec)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    result = {"correct": correct, "attempted": rec["attempted"],
              "failed": rec["failed"], "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                               "idle_gaps": rec["trace"]["idle_gaps"]}
    result["checks"] = checks
    print(f"[bench] {workload} seed={seed} setup_s={rec['setup_s']:.3f} "
          f"window_s={w1 - w0:.3f} compile_misses_in_window="
          f"{rec['window']['compile_misses']}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "BENCHMARK.json").is_file():
        print(f"bench: no BENCHMARK.json at {ROOT}", file=sys.stderr)
        return 2
    enable_compile_cache()
    return run_cell(args.workload, args.seed, args.seconds, bool(args.trace))


if __name__ == "__main__":
    sys.exit(main())
