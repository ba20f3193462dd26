#!/usr/bin/env python3
"""Time the scan MTTKRP's two reductions per nnz slot on a TPU, by mode length.

    python scripts/mxu_crossover.py [--rows 4096,16384,...] [--nnz N]

For each output-mode length ``I`` it makes a power-law tensor of ``I x 9184
x 12092`` (NELL-2's other two modes) with ``--nnz`` distinct nonzeros,
builds BLCO at the device's default launch size, and times the in-memory
mode-0 MTTKRP (``LaunchCache.mttkrp``, one ``stacked_mttkrp`` dispatch)
with the scatter-add reduction ``choose_resolution`` picks and with
``resolution="mxu"``, after one warm-up call of each.  The crossover of the
two times is ``repro.core.mttkrp.MXU_MAX_ROWS``.  It prints one JSON line
per length (nanoseconds per padded slot, the median of ``--reps`` calls, and
the largest difference of the two outputs relative to the largest entry)
and refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax                                                  # noqa: E402
import numpy as np                                          # noqa: E402

from repro import core                                      # noqa: E402
from repro.core.launches import LaunchCache                 # noqa: E402

OTHER_DIMS = (9184, 12092)


def time_calls(fn, reps: int) -> float:
    """Median seconds of ``reps`` calls, each waited for."""
    jax.block_until_ready(fn())
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        walls.append(time.perf_counter() - t0)
    return statistics.median(walls)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", default="4096,16384,32768,49152,65536,98304,"
                                      "131072")
    ap.add_argument("--nnz", type=int, default=4_000_000)
    ap.add_argument("--rank", type=int, default=32)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"mxu_crossover: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        return 3
    rng = np.random.default_rng(args.seed)
    for rows in (int(r) for r in args.rows.split(",")):
        dims = (rows,) + OTHER_DIMS
        t = core.random_tensor_exact(dims, args.nnz, seed=args.seed)
        cache = LaunchCache.from_blco(core.build_blco(t))
        factors = [jax.device_put(rng.standard_normal((d, args.rank))
                                  .astype(np.float32)) for d in dims]
        slots = cache.num_launches * cache.reservation
        line = {"rows": rows, "nnz": args.nnz, "slots": slots,
                "device": dev.device_kind}
        outs = {}
        for res in (core.choose_resolution(rows), "mxu"):
            sec = time_calls(lambda: cache.mttkrp(factors, 0, resolution=res),
                             args.reps)
            line[f"{res}_ns_per_slot"] = sec / slots * 1e9
            outs[res] = np.asarray(cache.mttkrp(factors, 0, resolution=res),
                                   np.float64)
        a, b = outs.values()
        line["rel_diff"] = float(np.max(np.abs(a - b)) / np.max(np.abs(a)))
        print(json.dumps(line), flush=True)
        cache.delete()
    return 0


if __name__ == "__main__":
    sys.exit(main())
