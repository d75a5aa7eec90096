#!/usr/bin/env python3
"""Readings from which the limits of ``correct`` are set, many seeds in one
process:

    python3 bench/calibrate.py --workload covtype_otf.fit --seeds 1,2,3 --seconds 0
    python3 bench/calibrate.py --workload covtype_otf.fit --seeds 1,2,3 --seconds 0 --fault half_batch

For each seed it sets the cell up, runs its window (``--seconds``; 0 runs
one fit), and prints one JSON line: the numbers ``correct`` compares for
the program against the float32 reference (``program``) and for the
control, the reference computed in three-pass bfloat16 and put in the
program's place (``control``). With ``--fault`` the program runs with that
fault of ``bench/faults.py`` planted, and ``control`` is not computed.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run, spec  # noqa: E402


def readings(cell, seed: int, seconds: float, devices, fault: str = "",
             control: bool = True) -> dict:
    """The compared numbers of one seed, for the program and the control."""
    from bench import drivers, faults, reference

    driver = drivers.make(cell, seed, seconds, devices, run.span)
    plant = faults.ALL[fault]() if fault else contextlib.nullcontext()
    with plant:
        driver.setup()
        rec = {}
        driver.window(seconds, rec)
    driver.release()
    out = {"seed": seed, "fault": fault or None,
           "attempted": rec["attempted"], "failed": rec["failed"]}
    if isinstance(driver, drivers.FitDriver):
        ref = driver.reference(reference.HIGHEST)
        out["program"] = driver.compare(driver.out, ref)
        out["evals"] = [rec["fits"][-1]["n_fg"], rec["fits"][-1]["n_hd"],
                        ref.n_fg, ref.n_hd]
        if control and not fault:
            ctl = driver.reference(reference.BF16X3)
            out["control"] = driver.compare(
                {"f_hist": ctl.f_hist, "gnorm": ctl.gnorm, "beta": ctl.beta},
                ref)
    else:
        x, got, unanswered = driver.served()
        ref = driver.reference(x, reference.HIGHEST)
        out["program"] = {"margin_gap": reference.rel_err(got, ref),
                          "unanswered": unanswered}
        if control and not fault:
            out["control"] = {"margin_gap": reference.rel_err(
                driver.reference(x, reference.BF16X3), ref)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--fault", default="")
    args = ap.parse_args(argv)
    run.enable_compile_cache()
    import jax
    cell = spec.load_cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"calibrate: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX sees {devs}", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        out = readings(cell, seed, args.seconds, devs[:cell.chips],
                       args.fault)
        out["seconds"] = time.perf_counter() - t
        print("CAL " + json.dumps(out, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
