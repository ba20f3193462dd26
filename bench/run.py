#!/usr/bin/env python3
"""On-chip benchmark of BLCO CP-ALS: one cell, one seed, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a tensor
configuration (``bench/configs/<config>.json``) and a regime
(``bench/traffic/<traffic>.json``: the device budget rule and the regime
``plan_for`` must pick under it; the ``sharded`` regime runs over a mesh
of the cell's chips).  The run:

1. fails without a TPU, with fewer chips than the cell asks for, or on a
   device kind missing from ``bench/peaks.json``;
2. set-up: makes the tensor from ``--seed`` (``bench/gen.py``), builds
   BLCO with the program's ``build_blco``, plans it with ``plan_for`` under
   the cell's budget, over its mesh where the regime is ``sharded``
   (asserting the cell's regime and no demotion), and warms up with CP-ALS
   sweeps of the cell's own shapes until one compiles nothing;
3. window: ``cp_als_step`` sweeps back to back until ``--seconds`` have
   passed; the sweep in progress then ends the window.  With ``--trace 1``
   the profiler records the window;
4. compares a sample of the window's MTTKRP results, mode updates and fits
   with the float64 reference (``bench/check.py``), after the plan is
   freed;
5. prints one JSON line: ``sweep_s`` and ``setup_s`` (``--trace 0``) or the
   cell's per-layer metrics, each read by ``bench/metrics/<name>.py``
   (``--trace 1``).

JAX's persistent compilation cache is kept at ``JAX_COMPILATION_CACHE_DIR``
where that is set, else at ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                              # noqa: E402
import gc                                                    # noqa: E402
import importlib.util                                        # noqa: E402
import json                                                  # noqa: E402
import os                                                    # noqa: E402
import resource                                              # noqa: E402
import shutil                                                # noqa: E402
import sys                                                   # noqa: E402
from pathlib import Path                                     # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_ROOT = ROOT / ".traces"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np                                           # noqa: E402

from bench import check, gen, reference, work                # noqa: E402

EXIT_NO_CHIP = 3
EXIT_UNKNOWN_DEVICE = 4
EXIT_WRONG_REGIME = 5
WARMUP_MAX_SWEEPS = 4
# The float32 matmuls of the mode update and the fit.  At JAX's default a
# TPU makes them in one bfloat16 pass, which no check can tell from the
# bfloat16 control; the program sets no precision of its own yet (PERF.md,
# Open questions), so the harness asks for float32 process-wide.
MATMUL_PRECISION = "highest"


def say(msg: str) -> None:
    print(msg, flush=True)


# --------------------------------------------------------------- cell files
def load_cell(name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration,
    traffic, limits and metric declarations."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def applies(metric):
        return name in metric.get("workloads", [name])

    return {
        "name": name,
        "chips": w["chips"],
        "config": json.loads((root / conf["file"]).read_text()),
        "traffic": json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text()),
        "limits": json.loads(
            (BENCH / "limits" / f"{name}.json").read_text())["limits"],
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def metric_reader(name: str):
    """``read(record) -> value | None`` of ``bench/metrics/<name>.py``."""
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------------ set-up
class CompileClock:
    """Backend compile (or persistent-cache load) time and count, from JAX's
    own monitoring events."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.programs += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def enable_compile_cache(jax) -> str:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def profiler_options():
    """Device ops, and host spans at the level of ``TraceAnnotation``; no
    Python call tracing and no HLO protos, which would make the trace of a
    streamed window hundreds of MB."""
    from jax.profiler import ProfileOptions
    opts = ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def device_budget(rule: dict, bytes_limits: list) -> int:
    """The device budget a traffic file's rule gives, from the
    ``bytes_limit`` of each of the cell's chips: each chip's less
    ``bytes``, summed over the chips."""
    if rule["rule"] == "device_limit_less":
        return sum(b - rule["bytes"] for b in bytes_limits)
    raise ValueError(f"unknown budget rule {rule['rule']!r}")


def cell_mesh(traffic: dict, devices: list):
    """The mesh of a ``sharded`` cell: its chips on ``data``, 1 on
    ``model`` (nnz split over the chips, factors replicated); None for
    any other regime."""
    if traffic["regime"] != "sharded":
        return None
    from jax.sharding import Mesh
    return Mesh(np.array(devices).reshape(len(devices), 1),
                ("data", "model"))


def chip_limits(devices: list) -> list:
    """The ``bytes_limit`` of each chip."""
    return [int(d.memory_stats()["bytes_limit"]) for d in devices]


def host_peak_rss() -> int:
    """Peak resident set of this process so far, in bytes."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def seeds(seed: int) -> dict:
    """Independent streams of one ``--seed``: tensor, init, check sample."""
    kids = np.random.SeedSequence(seed).spawn(3)
    return {"tensor": kids[0], "init": int(kids[1].generate_state(1)[0]),
            "check": kids[2]}


def stats_delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after
            if isinstance(after[k], (int, float)) and k in before}


# --------------------------------------------------------------------- run
def run_cell(cell: dict, seed: int, seconds: float, trace: bool, *,
             devices: list, bytes_limits: list, peaks: dict, t_start: float,
             clock: CompileClock, trace_dir: Path | None = None,
             controls: tuple = ()) -> dict:
    """Set up, measure and check one run of ``cell`` on ``devices`` (the
    cell's chips, with the ``bytes_limit`` of each); the result line.

    ``controls`` (precisions) also reads the check's numbers for the
    reference at each precision in the program's place, under
    ``"control"`` (``bench/calibrate.py``; the benchmark's runs do not).
    """
    import jax
    from jax.profiler import TraceAnnotation
    from repro import core
    from repro.core.tensor import SparseTensor
    from repro.engine import plan_for

    conf, traffic = cell["config"], cell["traffic"]
    dims, rank = tuple(conf["dims"]), conf["rank"]
    jax.config.update("jax_default_matmul_precision", MATMUL_PRECISION)
    streams = seeds(seed)
    compiled_at_start = (clock.seconds, clock.programs, clock.cache_hits)
    setup = {}

    t0 = time.perf_counter()
    indices, values = gen.frostt_tensor(dims, conf["nnz"],
                                        streams["tensor"])
    tensor = SparseTensor(dims, indices, values)
    setup["generate_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    blco = core.build_blco(tensor)
    setup["blco_build_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    budget = device_budget(traffic["budget"], bytes_limits)
    mesh = cell_mesh(traffic, devices)
    plan = plan_for(blco, budget, rank=rank, mesh=mesh)
    demotions = plan.stats().demotions
    say(f"plan: {plan.backend} under a budget of {budget:,} B"
        + (f" over a mesh {dict(mesh.shape)}" if mesh is not None else "")
        + ", "
        f"{len(blco.launches)} launches, {plan.device_bytes():,} B on the "
        f"device, {demotions} demotions")
    if plan.backend != traffic["regime"] or demotions:
        print(f"bench: regime {plan.backend} with {demotions} demotions, "
              f"the cell needs {traffic['regime']} with none",
              file=sys.stderr)
        sys.exit(EXIT_WRONG_REGIME)
    norm_x = reference.norm(values)
    state = core.cp_als_init(dims, rank, norm_x=norm_x, tol=0.0,
                             seed=streams["init"])
    jax.block_until_ready(state.factors)
    setup["plan_upload_s"] = time.perf_counter() - t0

    rec = check.Recorder(plan, TraceAnnotation)
    t0 = time.perf_counter()
    # until a sweep compiles nothing: the sharded plan compiles each mode
    # again in its second sweep, which meets the factors on the mesh where
    # the first met them on one chip
    for sweeps in range(1, WARMUP_MAX_SWEEPS + 1):
        programs = clock.programs
        rec.begin_sweep()
        core.cp_als_step(rec, state)
        rec.end_sweep(state)
        if clock.programs == programs:
            break
    setup["warmup_s"] = time.perf_counter() - t0
    setup["warmup_sweeps"] = sweeps
    setup["compile_s"] = clock.seconds - compiled_at_start[0]
    setup["programs"] = clock.programs - compiled_at_start[1]
    setup["cache_hits"] = clock.cache_hits - compiled_at_start[2]
    rec.sweeps.clear()
    # set-up's objects out of the collector's way: no full collection of
    # them inside the window
    gc.collect()
    gc.freeze()
    compiled_before = clock.programs
    stats_before = plan.stats().snapshot()

    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=profiler_options())
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    walls = []
    with TraceAnnotation("bench.window"):
        while True:
            ts = time.perf_counter()
            rec.begin_sweep()
            with TraceAnnotation("bench.sweep"):
                core.cp_als_step(rec, state)
                rec.end_sweep(state)
            walls.append(time.perf_counter() - ts)
            if time.perf_counter() - t_window >= seconds:
                break
    window_s = time.perf_counter() - t_window
    gc.unfreeze()
    if trace:
        jax.profiler.stop_trace()
    compiled_in_window = clock.programs - compiled_before
    stats = stats_delta(plan.stats().snapshot(), stats_before)
    # the fullest of the cell's chips
    peak_bytes = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                     for d in devices)
    plan.close()
    del blco

    say("set-up: " + ", ".join(f"{k} {v:.2f} s" if isinstance(v, float)
                               else f"{k} {v}" for k, v in setup.items())
        + f"; setup_s {setup_s:.2f} s")
    say(f"window: {len(walls)} sweeps in {window_s:.3f} s "
        f"({', '.join(f'{w:.3f}' for w in walls)} s), "
        f"fits {[round(s.fit, 6) for s in rec.sweeps]}, "
        f"{compiled_in_window} programs compiled inside it; host peak RSS "
        f"{host_peak_rss():,} B")
    slow = max(range(len(walls)), key=walls.__getitem__)
    say(f"slowest sweep {slow}, host seconds: " + ", ".join(
        f"{n} {d:.3f}" for n, d in rec.sweeps[slow].phases()))

    t0 = time.perf_counter()
    coo = reference.COO(indices, values, dims)
    del tensor, indices
    result = check.compare(rec.sweeps, coo, rank, norm_x,
                           np.random.default_rng(streams["check"]),
                           controls=controls)
    correct = check.verdict(result, cell["limits"])
    say(f"check: sweeps {result['sweeps']}, rank columns {result['cols']}, "
        f"condition of V up to {result['condition']:.4g}, "
        f"{time.perf_counter() - t0:.2f} s, host peak RSS {host_peak_rss():,} B"
        + "".join(
            f"; fault: {f}" for f in result["faults"]))

    record = {
        "dims": dims, "nnz": conf["nnz"], "rank": rank,
        "value_dtype": conf["dtype"], "peaks": peaks, "chips": len(devices),
        "sweeps": len(walls),
        "call_modes": [m for s in rec.sweeps for _, m, _ in s.calls],
        "stats": stats, "memory_peak_bytes": peak_bytes, "setup": setup,
        "trace": None,
    }
    out_device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": peak_bytes}
    line = {"correct": correct, "attempted": len(walls),
            "failed": 0 if correct else max(1, len(result["sweeps"]))}
    if trace:
        from bench import xplane
        path = xplane.find_xplane(str(trace_dir))
        record["trace"] = xplane.load(path) if path else None
        metrics = {}
        for m in cell["per_layer"]:
            value = metric_reader(m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["metrics"] = metrics
        tr = record["trace"]
        if tr is not None:
            w0, w1 = tr.window()
            out_device["busy_s"] = tr.busy_ns() / 1e9
            out_device["window_s"] = (w1 - w0) / 1e9
            line["device"] = out_device
            line["breakdown"] = {"device_ops": tr.top_ops(),
                                 "idle_gaps": tr.idle_gaps()}
        else:
            line["device"] = out_device
    else:
        values_e2e = {"sweep_s": window_s / len(walls), "setup_s": setup_s}
        line["metrics"] = {m["name"]: {"value": values_e2e[m["name"]],
                                       "unit": m["unit"]}
                           for m in cell["end_to_end"]}
        line["device"] = out_device
    line["checks"] = {k: {"value": result["numbers"][k],
                          "limit": cell["limits"][k]}
                      for k in check.NUMBERS}
    if result["faults"]:
        line["checks"]["faults"] = {"value": len(result["faults"]),
                                    "limit": 0}
    if controls:
        line["control"] = result["control"]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        print(f"bench: the cell needs {cell['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return EXIT_NO_CHIP
    try:
        peaks = work.peaks_for(devs[0].device_kind)
    except KeyError as e:
        print(f"bench: {e}", file=sys.stderr)
        return EXIT_UNKNOWN_DEVICE
    cache = enable_compile_cache(jax)
    say(f"device: {devs[0].device_kind} x{len(devs)}, compile cache {cache}")
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                    devices=devs[:cell["chips"]],
                    bytes_limits=chip_limits(devs[:cell["chips"]]),
                    peaks=peaks, t_start=T_START, clock=CompileClock(jax),
                    trace_dir=TRACE_ROOT / args.workload)
    for k, v in line["checks"].items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
