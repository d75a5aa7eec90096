#!/usr/bin/env python3
"""Sweep of offered rates for an open-loop cell, to find its knee:

    python3 bench/knee.py --workload covtype_otf.serve_poisson --rates 500,1000,2000 --seconds 10

Sets the cell up once (its configuration and mix, at the mix's own rate for
nothing but the rows), then for each rate sends a Poisson schedule of that
rate for ``--seconds`` and prints one JSON line: the offered and completed
rates, requests refused or failed, the median and 99th-percentile latency,
and the median latency of the first and last tenth of the requests. A step
keeps up when nothing is refused and the last tenth waits no longer than
the first (no backlog grows). The knee is the highest rate that keeps up.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402

from bench import run, spec, stats  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    run.enable_compile_cache()
    import jax

    from bench import drivers
    cell = spec.load_cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"knee: no TPU (JAX sees {devs})", file=sys.stderr)
        return 2
    driver = drivers.make(cell, args.seed, args.seconds, devs[:cell.chips],
                          run.span)
    driver.setup()
    for rate in (float(r) for r in args.rates.split(",")):
        driver.plan(dict(cell.traffic, rate_profile=[[1.0, rate]]),
                    args.seconds)
        rec = {}
        driver.window(args.seconds, rec)
        lat = rec["serve"]["latency_s"]
        done = driver.loop.done[~np.isnan(driver.loop.done)]
        tenth = max(1, len(lat) // 10)
        first = stats.percentile_ms(lat[:tenth], 50)
        last = stats.percentile_ms(lat[-tenth:], 50)
        span = done.max() - driver.loop.due[0] if len(done) else float("nan")
        print(json.dumps({
            "rate": rate, "requests": rec["attempted"],
            "failed": rec["failed"], "completed_per_s": len(done) / span,
            "p50_ms": stats.percentile_ms(lat, 50),
            "p99_ms": stats.percentile_ms(lat, 99),
            "first_tenth_p50_ms": first, "last_tenth_p50_ms": last,
            "late_p99_ms": stats.percentile_ms(rec["serve"]["late_s"], 99),
            "occupancy": rec["serve"]["dispatched_rows"]
            / max(1, rec["serve"]["padded_rows"]),
            "keeps_up": rec["failed"] == 0 and last <= 2 * first + 5.0}),
            flush=True)
    driver.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
