#!/usr/bin/env python3
"""Readings that the limits in ``bench/limits/<cell>.json`` are set from.

    python bench/calibrate.py --workload <cell> --seeds 11 --seconds 12

One process on the cell's chips: for each seed a whole run of the cell
(tensor, BLCO, plan, warm-up, window, comparison), printing as one JSON
line per seed the check's three numbers for the program and for the
control, the reference computed in bfloat16 and put in the program's
place.  The lower reading of a number is the largest the program gives
over the seeds; the upper, the smallest the control gives.  The
benchmark's own runs never run the control.  Give each process one seed,
as a benchmark run has: a process's host memory only grows from seed to
seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run

# the control of every number: the reference in bfloat16
CONTROL = "bfloat16"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"calibrate: needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return run.EXIT_NO_CHIP
    devs = devs[:cell["chips"]]
    peaks = run.work.peaks_for(devs[0].device_kind)
    run.enable_compile_cache(jax)
    clock = run.CompileClock(jax)
    program, control = {}, {}
    for seed in args.seeds:
        line = run.run_cell(cell, seed, args.seconds, False, devices=devs,
                            bytes_limits=run.chip_limits(devs), peaks=peaks,
                            t_start=time.perf_counter(), clock=clock,
                            controls=(CONTROL,))
        got = {k: v["value"] for k, v in line["checks"].items()}
        print(json.dumps({"seed": seed, "correct": line["correct"],
                          "program": got, "control": line["control"],
                          "sweep_s": line["metrics"]["sweep_s"]["value"],
                          "host_peak_rss": run.host_peak_rss()}),
              flush=True)
        for k, v in got.items():
            program[k] = max(program.get(k, 0.0), v)
        for k, v in line["control"][CONTROL].items():
            control[k] = min(control.get(k, float("inf")), v)
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "lower": program, "upper": control}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
