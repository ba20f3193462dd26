"""Benchmarks — one per paper table/figure, CPU-scale analogues.

  fig1   per-mode time variation of a mode-specific format vs BLCO
  fig8   all-mode MTTKRP speedup: BLCO vs COO / F-COO / CSF (geomean)
  fig9   per-mode speedup vs the strongest baseline
  table3 memory volume (device bytes) per format + achieved throughput
  fig10  out-of-memory streaming: overall vs in-memory throughput
  fig11  format construction cost: BLCO vs baselines (+ ALTO stages)
  fig12  BLCO construction-stage breakdown
  embed  the technique in the LM path: segment vs scatter embed-grad step
  bench5 memory-hierarchy MTTKRP: in-memory vs host-streamed vs
         disk-streamed store (BENCH_5.json)
  bench6 observability: traced disk-streamed CP-ALS with span-vs-stats
         consistency + tracing overhead (BENCH_6.json, TRACE_6.json)

Output: ``name,us_per_call,derived`` CSV rows (plus commentary lines
prefixed with '#'). The paper's absolute GPU numbers are not reproducible
on 1 CPU core; the *relative* claims (BLCO >= baselines on all-mode MTTKRP,
mode-balance, OOM parity) are what these measure — see EXPERIMENTS.md.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import time

import numpy as np

from repro import core

RANK = 32
SUITE = ["uber-like", "chicago-like", "vast-like", "darpa-like",
         "nell2-like"]

# block budget for the dispatch bench: forces multi-launch builds (tens to
# ~150 launches) on the fig8 suite — the hypersparse many-block regime the
# paper's launch batching targets, where per-launch dispatch + host padding
# overhead dominates the per-launch loop
DISPATCH_BLOCK = 1 << 9


def _time(fn, *, warmup=2, iters=5) -> float:
    r = None
    for _ in range(warmup):
        r = fn()
    if hasattr(r, "block_until_ready"):
        r.block_until_ready()
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        r = fn()
        if hasattr(r, "block_until_ready"):
            r.block_until_ready()
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


def _factors(t, seed=0):
    rng = np.random.default_rng(seed)
    import jax.numpy as jnp
    return [jnp.asarray(rng.standard_normal((d, RANK)).astype(np.float32))
            for d in t.dims]


def _formats(t):
    """Device-resident ExecutionPlans (paper's in-memory regime: the tensor
    stays in device memory across CP-ALS iterations; only factors change).
    Every format — BLCO and baselines — goes through the one engine API."""
    from repro.engine import plan_for
    b = core.build_blco(t)
    budget = 1 << 40                      # in-memory benchmarking: no limit
    return {
        "blco": plan_for(b, budget, rank=RANK, backend="in_memory"),
        "coo": plan_for(b, budget, rank=RANK, backend="coo", tensor=t),
        "fcoo": plan_for(b, budget, rank=RANK, backend="fcoo", tensor=t),
        "csf": plan_for(b, budget, rank=RANK, backend="csf", tensor=t),
    }


def _mttkrp_time(fmt_name, fmt, factors, mode, resolution="auto") -> float:
    if fmt_name == "blco":
        return _time(lambda: fmt.mttkrp(factors, mode, resolution=resolution))
    return _time(lambda: fmt.mttkrp(factors, mode))


def bench_fig8_fig9_fig1(rows):
    geo: dict[str, list] = {"coo": [], "fcoo": [], "csf": []}
    geo_faithful: dict[str, list] = {"coo": [], "fcoo": [], "csf": []}
    for name in SUITE:
        t = core.paper_like(name, seed=0)
        fmts = _formats(t)
        factors = _factors(t)
        per_mode: dict[str, list] = {k: [] for k in fmts}
        faithful: list[float] = []
        for mode in range(t.order):
            for k in fmts:
                res = "direct" if k == "blco" else "auto"
                per_mode[k].append(_mttkrp_time(k, fmts[k], factors, mode,
                                                resolution=res))
            # paper-faithful conflict-resolution path (segment machinery);
            # on CPU the direct scatter wins — the segment win is TPU/GPU-
            # specific (serialized conflicting updates), see EXPERIMENTS.md
            faithful.append(_mttkrp_time("blco", fmts["blco"], factors, mode,
                                         resolution="auto"))
        all_mode = {k: sum(v) for k, v in per_mode.items()}
        t_faithful = sum(faithful)
        for k in ("coo", "fcoo", "csf"):
            sp = all_mode[k] / all_mode["blco"]
            geo[k].append(sp)
            geo_faithful[k].append(all_mode[k] / t_faithful)
            rows.append((f"fig8.{name}.speedup_vs_{k}",
                         all_mode["blco"] * 1e6, f"{sp:.3f}x"))
        rows.append((f"fig8.{name}.faithful_segment_path",
                     t_faithful * 1e6,
                     f"{all_mode['blco']/t_faithful:.3f}x of direct"))
        # fig9: per-mode speedup vs best baseline
        for mode in range(t.order):
            best = min(per_mode[k][mode] for k in ("coo", "fcoo", "csf"))
            rows.append((f"fig9.{name}.mode{mode+1}",
                         per_mode["blco"][mode] * 1e6,
                         f"{best / per_mode['blco'][mode]:.3f}x"))
        # fig1: per-mode imbalance (max/min across modes), CSF vs BLCO
        for k in ("csf", "blco"):
            imb = max(per_mode[k]) / min(per_mode[k])
            rows.append((f"fig1.{name}.mode_imbalance_{k}", 0.0,
                         f"{imb:.2f}x"))
    for k, v in geo.items():
        g = float(np.exp(np.mean(np.log(v))))
        rows.append((f"fig8.geomean_speedup_vs_{k}", 0.0, f"{g:.3f}x"))
    for k, v in geo_faithful.items():
        g = float(np.exp(np.mean(np.log(v))))
        rows.append((f"fig8.geomean_faithful_vs_{k}", 0.0, f"{g:.3f}x"))


def bench_table3(rows):
    for name in SUITE[:3]:
        t = core.paper_like(name, seed=0)
        fmts = _formats(t)
        factors = _factors(t)
        vol = {k: f.device_bytes() for k, f in fmts.items()}
        for k, b in vol.items():
            tm = sum(_mttkrp_time(k, fmts[k], factors, m)
                     for m in range(t.order))
            tp = b * t.order / tm / 1e9
            rows.append((f"table3.{name}.{k}", tm * 1e6,
                         f"vol={b/1e6:.2f}MB tp={tp:.2f}GB/s"))


def bench_fig10(rows):
    from repro.engine import plan_for
    t = core.paper_like("amazon-like", seed=0)
    b = core.build_blco(t, max_nnz_per_block=1 << 14)
    factors = _factors(t)
    dev = plan_for(b, 1 << 40, rank=RANK, backend="in_memory")
    in_mem = _time(lambda: dev.mttkrp(factors, 0))
    stream = plan_for(b, 1 << 40, rank=RANK, backend="streamed", queues=4)
    t0 = time.perf_counter()
    stream.mttkrp(factors, 0)
    overall = time.perf_counter() - t0
    nnz_bytes = core.format_bytes(b)
    s = stream.stats()
    rows.append(("fig10.amazon-like.in_memory", in_mem * 1e6,
                 f"{nnz_bytes/in_mem/1e9:.2f}GB/s"))
    rows.append(("fig10.amazon-like.oom_overall", overall * 1e6,
                 f"{nnz_bytes/overall/1e9:.2f}GB/s "
                 f"({in_mem/overall*100:.0f}% of in-mem)"))
    rows.append(("fig10.amazon-like.h2d_bytes", 0.0,
                 f"{s.h2d_bytes/1e6:.1f}MB"))
    rows.append(("fig10.amazon-like.put_vs_device", s.put_time_s * 1e6,
                 f"device={s.device_time_s*1e6:.0f}us "
                 f"dispatch={s.dispatch_time_s*1e6:.0f}us"))
    dev.close()
    stream.close()


def bench_fig11_fig12(rows):
    for name in SUITE[:3]:
        t = core.paper_like(name, seed=0)
        tb = _time(lambda: core.build_blco(t), warmup=1, iters=3)
        tc = _time(lambda: core.COOFormat.build(t), warmup=1, iters=3)
        tf = _time(lambda: core.FCOOFormat.build(t), warmup=1, iters=3)
        ts = _time(lambda: core.CSFFormat.build(t), warmup=1, iters=3)
        rows.append((f"fig11.{name}.blco", tb * 1e6, "1.00x"))
        for k, v in (("coo", tc), ("fcoo", tf), ("csf", ts)):
            rows.append((f"fig11.{name}.{k}", v * 1e6, f"{v/tb:.2f}x vs blco"))
        b = core.build_blco(t)
        total = sum(b.construction_stats.values())
        for stage, sec in b.construction_stats.items():
            rows.append((f"fig12.{name}.{stage}", sec * 1e6,
                         f"{sec/total*100:.1f}%"))
        # paper claim: blocking+re-encoding < 25% of construction
        extra = (b.construction_stats["reencode"]
                 + b.construction_stats["blocking"]
                 + b.construction_stats["block_keys"]
                 + b.construction_stats["batching"])
        rows.append((f"fig12.{name}.blco_extra_over_alto", extra * 1e6,
                     f"{extra/total*100:.1f}% (<25% claim)"))


def bench_embed_grad(rows):
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch import steps
    from repro.optim import adamw
    from repro.models import build_model
    rng = np.random.default_rng(0)
    for method in ("segment", "scatter"):
        cfg = dataclasses.replace(get_config("minicpm_2b").reduced(),
                                  embed_grad=method)
        model = build_model(cfg)
        opt_cfg = adamw.AdamWConfig(total_steps=100)
        step = jax.jit(steps.make_train_step(cfg, opt_cfg))
        params = model.init(jax.random.key(0))
        state = {"params": params, "opt": adamw.init_state(params, opt_cfg)}
        batch = {"tokens": jnp.asarray(
                     (rng.zipf(1.2, (8, 256)) % cfg.vocab_size).astype(np.int32)),
                 "labels": jnp.asarray(
                     rng.integers(0, cfg.vocab_size, (8, 256)))}

        def run():
            nonlocal state
            state, m = step(state, batch)
            return m["loss"]
        rows.append((f"embed.train_step.{method}", _time(run) * 1e6, ""))


def bench_service(rows):
    """Multi-tenant service throughput: jobs/sec at 1 vs 4 concurrent tenants.

    Same total work (4 jobs, 2 distinct tensors, fixed iteration count) run
    (a) sequentially through 4 single-job service instances and (b) through
    one shared service — the shared run reuses cached BLCO builds and pooled
    reservations, so its jobs/sec measures the serving layer's win.
    """
    from repro.service import (BuildParams, DecompositionService,
                               SubmitDecomposition)
    build = BuildParams(max_nnz_per_block=1 << 12)
    tensors = [core.paper_like("uber-like", seed=0),
               core.paper_like("chicago-like", seed=0)]
    reqs = [SubmitDecomposition(tensor=tensors[i % 2], rank=16, iters=4,
                                tol=0.0, seed=i, build=build)
            for i in range(4)]

    def run_sequential():
        for req in reqs:
            svc = DecompositionService(device_budget_bytes=8 << 20, queues=4)
            svc.submit(req)
            svc.run()

    def run_shared():
        svc = DecompositionService(device_budget_bytes=8 << 20, queues=4)
        for req in reqs:
            svc.submit(req)
        svc.run()
        return svc

    # untimed warm-up so neither variant pays launch_mttkrp compilation
    # (the jit cache is process-wide; without this the first-timed variant
    # absorbs all compile time and the ratio is meaningless)
    warm = DecompositionService(device_budget_bytes=8 << 20, queues=4)
    for t in tensors:
        warm.submit(SubmitDecomposition(tensor=t, rank=16, iters=1, tol=0.0,
                                        seed=0, build=build))
    warm.run()

    t0 = time.perf_counter()
    run_sequential()
    seq_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    svc = run_shared()
    shared_s = time.perf_counter() - t0
    m = svc.service_metrics()
    rows.append(("service.1_tenant_sequential", seq_s / len(reqs) * 1e6,
                 f"{len(reqs)/seq_s:.3f}jobs/s"))
    rows.append(("service.4_tenants_shared", shared_s / len(reqs) * 1e6,
                 f"{len(reqs)/shared_s:.3f}jobs/s "
                 f"({seq_s/shared_s:.2f}x, {m['blco_cache_hits']} cache hits, "
                 f"peak_res={m['peak_admitted_reservation_bytes']/1e6:.2f}MB)"))


def bench_multitenant(rows, *, fast: bool = False,
                      json_path: str | None = "BENCH_4.json") -> dict:
    """Weighted multi-tenant serving through the async runtime (ISSUE 4).

    Drives N concurrent tenants with mixed fair-share weights through
    ``ServiceRuntime`` (worker thread + stride scheduler), ending together
    by giving each tenant an iteration cap proportional to its weight, and
    records per-tenant iterations/sec + achieved vs expected share into
    ``BENCH_4.json``.  A sacrificial tenant is cancelled mid-run to record
    the measured pooled-byte release of ``cancel()``.
    """
    from repro.service import (BuildParams, CancelJob, ServiceRuntime,
                               SubmitDecomposition)
    build = BuildParams(max_nnz_per_block=1 << 12)
    t_small = core.paper_like("uber-like", seed=0)
    t_big = core.paper_like("chicago-like", seed=0)
    base_iters = 2 if fast else 6
    rank = 8 if fast else 16
    # mixed weights; the heavy tenant does proportionally more sweeps
    tenants = [("heavy", 2.0, t_small), ("light-1", 1.0, t_small),
               ("light-2", 1.0, t_big)]
    total_w = sum(w for _, w, _ in tenants)

    # untimed warm-up so compile time does not skew the shared run
    warm = ServiceRuntime(device_budget_bytes=64 << 20, queues=4)
    with warm:
        for i, t in enumerate((t_small, t_big)):
            warm.submit(SubmitDecomposition(tensor=t, rank=rank, iters=1,
                                            tol=0.0, seed=i, build=build))
        warm.drain(timeout=600)

    # enqueue every weighted tenant BEFORE the worker starts: submitting
    # into a live worker lets the first tenant burn through its capped
    # sweeps while the rest are still being registered, which skews the
    # measured share window (all of it spent on one tenant)
    rt = ServiceRuntime(device_budget_bytes=64 << 20, queues=4)
    job_tenant = {}
    for i, (name, w, t) in enumerate(tenants):
        job_tenant[rt.submit(SubmitDecomposition(
            tensor=t, rank=rank, iters=int(base_iters * w), tol=0.0,
            seed=i, build=build, tenant=name, weight=w))] = name
    with rt:
        t0 = time.perf_counter()
        victim = rt.submit(SubmitDecomposition(
            tensor=t_big, rank=rank, iters=10_000, tol=0.0, seed=9,
            build=build, tenant="victim", weight=0.5))
        vfeed = rt.subscribe(victim)
        vfeed.get(timeout=600)               # victim really ran a sweep
        freed = rt.cancel(CancelJob(job_id=victim)).freed_bytes
        rt.unsubscribe(vfeed)
        rt.drain(timeout=600)
        wall = time.perf_counter() - t0
        m = rt.service_metrics()
        trace = list(rt.scheduler.trace)

    # share is measured over the FIRST HALF of the weighted tenants'
    # iteration trace — a window where no tenant has hit its cap yet, so
    # an unfair scheduler (e.g. FIFO serialization) would visibly skew it;
    # over the whole run the caps themselves would mask any unfairness
    tenant_trace = [job_tenant[j] for j in trace if j in job_tenant]
    window = tenant_trace[:len(tenant_trace) // 2] or tenant_trace
    per_tenant: dict[str, dict] = {}
    max_dev = 0.0
    for name, w, t in tenants:
        n = m["tenant_iterations"].get(name, 0)
        expected = w / total_w
        share = window.count(name) / len(window)
        dev = abs(share - expected) / expected
        max_dev = max(max_dev, dev)
        per_tenant[name] = {
            "weight": w, "nnz": t.nnz, "iterations": n,
            "iters_per_sec": n / wall if wall > 0 else 0.0,
            "share": share, "expected_share": expected,
        }
        rows.append((f"service4.{name}", wall / max(1, n) * 1e6,
                     f"w={w} {n / wall:.2f}it/s share={share:.3f} "
                     f"(want {expected:.3f})"))
    rows.append(("service4.max_share_deviation", 0.0, f"{max_dev:.3f}"))
    rows.append(("service4.cancel_freed_bytes", 0.0, f"{freed/1e6:.2f}MB"))
    payload = {
        "bench": "weighted_multi_tenant_service",
        "fast_mode": fast,
        "rank": rank,
        "backend": _jax_backend(),
        "note": ("N concurrent tenants with mixed stride-scheduling "
                 "weights through the async ServiceRuntime; iteration caps "
                 "proportional to weights so tenants finish together.  The "
                 "achieved share is measured over the first half of the "
                 "iteration trace (no tenant capped yet), so scheduler "
                 "unfairness cannot hide behind the caps.  A sacrificial "
                 "tenant is cancelled mid-run; freed bytes are the "
                 "measured admission-budget release."),
        "tenants": per_tenant,
        "wall_s": wall,
        "iterations_per_sec_total": m["iterations_total"] / wall
        if wall > 0 else 0.0,
        "max_share_deviation_vs_weights": max_dev,
        "victim_iterations_before_cancel":
            m["tenant_iterations"].get("victim", 0),
        "cancelled_jobs": m["jobs_cancelled"],
        "cancel_freed_bytes": freed,
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
    return payload


def bench_dispatch(rows, *, fast: bool = False,
                   json_path: str | None = "BENCH_3.json") -> dict:
    """Single-dispatch launch-cache paths vs the PR-2 per-launch loop.

    Per fig8-suite tensor (built with a small block budget so the BLCO has
    MANY launches — the regime the paper's "reduce kernel launching
    overhead" claim is about), measures us_per_call of:

      per_launch_loop   PR-2 hot path: one numpy padding pass + one XLA
                        dispatch per launch per call (``mttkrp_per_launch``)
      cached_scan_xla   launch cache + single jitted lax.scan dispatch
                        (``InMemoryPlan(kernel="xla")``)
      fused_pallas      launch cache + ONE fused pallas_call pipeline
                        (``InMemoryPlan(kernel="pallas")``; interpret mode
                        on CPU — the Pallas timings here measure the
                        interpreter, not TPU performance)
      phases_pallas     PR-2 three-dispatch Pallas pipeline (cache-driven)

    Emits the machine-readable ``BENCH_3.json`` next to the CSV rows.
    """
    from repro.engine import plan_for
    from repro.kernels import pallas_mttkrp_phases

    suite = SUITE[:2] if fast else SUITE
    iters = 2 if fast else 5
    warmup = 1 if fast else 2
    p_iters = 1 if fast else 3
    suites: dict[str, dict] = {}
    speedups = []
    for name in suite:
        t = core.paper_like(name, seed=0)
        b = core.build_blco(t, max_nnz_per_block=DISPATCH_BLOCK)
        factors = _factors(t)
        mode = 0

        c0 = core.dispatch_count()
        core.mttkrp_per_launch(b, factors, mode)
        loop_dispatches = core.dispatch_count() - c0
        t_loop = _time(lambda: core.mttkrp_per_launch(b, factors, mode),
                       warmup=warmup, iters=iters)

        plan_x = plan_for(b, 1 << 40, rank=RANK, backend="in_memory",
                          kernel="xla")
        c0 = core.dispatch_count()
        plan_x.mttkrp(factors, mode)
        scan_dispatches = core.dispatch_count() - c0
        t_scan = _time(lambda: plan_x.mttkrp(factors, mode),
                       warmup=warmup, iters=iters)

        plan_p = plan_for(b, 1 << 40, rank=RANK, backend="in_memory",
                          kernel="pallas")
        t_fused = _time(lambda: plan_p.mttkrp(factors, mode),
                        warmup=1, iters=p_iters)
        t_phases = _time(lambda: pallas_mttkrp_phases(b, factors, mode),
                         warmup=1, iters=p_iters)

        sp = t_loop / t_scan
        speedups.append(sp)
        suites[name] = {
            "nnz": t.nnz,
            "launches": len(b.launches),
            "per_launch_loop_us": t_loop * 1e6,
            "cached_scan_xla_us": t_scan * 1e6,
            "fused_pallas_us": t_fused * 1e6,
            "phases_pallas_us": t_phases * 1e6,
            "dispatches_per_call_loop": loop_dispatches,
            "dispatches_per_call_cached": scan_dispatches,
            "speedup_cached_scan_vs_loop": sp,
        }
        rows.append((f"bench3.{name}.per_launch_loop", t_loop * 1e6,
                     f"{loop_dispatches} dispatches/call"))
        rows.append((f"bench3.{name}.cached_scan_xla", t_scan * 1e6,
                     f"{scan_dispatches} dispatch/call {sp:.2f}x vs loop"))
        rows.append((f"bench3.{name}.fused_pallas", t_fused * 1e6,
                     "1 dispatch/call (interpret)"))
        rows.append((f"bench3.{name}.phases_pallas", t_phases * 1e6,
                     "3-phase (interpret)"))
        plan_x.close()
        plan_p.close()

    geo = float(np.exp(np.mean(np.log(speedups))))
    rows.append(("bench3.geomean_cached_scan_vs_loop", 0.0, f"{geo:.3f}x"))
    payload = {
        "bench": "fused_single_dispatch_blco_mttkrp",
        "fast_mode": fast,
        "rank": RANK,
        "block_budget_nnz": DISPATCH_BLOCK,
        "backend": _jax_backend(),
        "note": ("Pallas paths run in interpret mode on CPU; their times "
                 "measure the interpreter.  The headline comparison is "
                 "cached_scan_xla (one dispatch, zero per-call host work) "
                 "vs per_launch_loop (the PR-2 engine hot path)."),
        "suites": suites,
        "geomean_speedup_cached_scan_vs_per_launch_loop": geo,
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
    return payload


def bench_oom(rows, *, fast: bool = False,
              json_path: str | None = "BENCH_5.json",
              store_dir: str | None = None) -> dict:
    """Memory-hierarchy MTTKRP (ISSUE 5): in-memory vs host-streamed vs
    DISK-streamed, equal to f32 rounding, through one engine API.

    Builds a many-launch BLCO, spills it to the persistent store
    (measuring write cost + file size), and times a full MTTKRP per tier:

      in_memory      device-resident launch cache, zero per-call H2D
      host_streamed  host-resident tensor, lazily padded chunks through
                     fixed reservations (the paper's OOM regime)
      disk_streamed  mmap'd store chunks straight to the device; host
                     window bounded by queues x reservation

    Records the bounded-window ratio (host window / all-launches padded
    bytes) — the quantity the lazy-padding fix and the store exist for —
    into ``BENCH_5.json``.
    """
    import shutil
    import tempfile
    from repro.engine import plan_for
    from repro.store import DiskStreamedPlan, open_blco, save_blco

    name = "uber-like" if fast else "amazon-like"
    block = 1 << 11 if fast else 1 << 12    # many launches: real streaming
    iters = 2 if fast else 5
    warmup = 1 if fast else 2
    queues = 4
    t = core.paper_like(name, seed=0)
    b = core.build_blco(t, max_nnz_per_block=block)
    factors = _factors(t)
    mode = 0
    own_dir = tempfile.mkdtemp() if store_dir is None else None
    sdir = store_dir or own_dir
    os.makedirs(sdir, exist_ok=True)
    path = f"{sdir}/bench_oom.blco"

    mem = host = disk = None
    try:
        t0 = time.perf_counter()
        file_bytes = save_blco(b, path)
        save_s = time.perf_counter() - t0

        mem = plan_for(b, 1 << 40, rank=RANK, backend="in_memory")
        host = plan_for(b, 1 << 40, rank=RANK, backend="streamed",
                        queues=queues)
        disk = DiskStreamedPlan(open_blco(path), queues=queues)

        t_mem = _time(lambda: mem.mttkrp(factors, mode),
                      warmup=warmup, iters=iters)
        t_host = _time(lambda: host.mttkrp(factors, mode),
                       warmup=warmup, iters=iters)
        t_disk = _time(lambda: disk.mttkrp(factors, mode),
                       warmup=warmup, iters=iters)

        # equal across all three tiers to f32 rounding (cheap insurance):
        # the tiers run separately compiled programs whose sums XLA may
        # associate differently, so within 1e-6 of the largest output
        m0 = np.asarray(mem.mttkrp(factors, mode))
        atol = 1e-6 * float(np.max(np.abs(m0)))
        if not (np.allclose(m0, np.asarray(host.mttkrp(factors, mode)),
                            rtol=0, atol=atol)
                and np.allclose(m0, np.asarray(disk.mttkrp(factors, mode)),
                                rtol=0, atol=atol)):
            raise AssertionError("memory-tier MTTKRP results diverged")

        nnz_bytes = core.format_bytes(b)
        window = disk.host_window_bytes()
        all_padded = disk.spec.bytes_per_launch * len(b.launches)
        ds = disk.stats()
    finally:
        for plan in (mem, host, disk):
            if plan is not None:
                plan.close()
        if own_dir is not None:
            shutil.rmtree(own_dir, ignore_errors=True)
    variants = {
        "in_memory": t_mem, "host_streamed": t_host, "disk_streamed": t_disk,
    }
    for k, sec in variants.items():
        rows.append((f"bench5.{name}.{k}", sec * 1e6,
                     f"{nnz_bytes/sec/1e9:.2f}GB/s "
                     f"({t_mem/sec*100:.0f}% of in-mem)"))
    rows.append((f"bench5.{name}.store_write", save_s * 1e6,
                 f"{file_bytes/1e6:.1f}MB file"))
    rows.append((f"bench5.{name}.host_window", 0.0,
                 f"{window/1e6:.2f}MB vs {all_padded/1e6:.2f}MB all-launch "
                 f"({window/all_padded:.3f}x)"))
    payload = {
        "bench": "memory_hierarchy_mttkrp",
        "fast_mode": fast,
        "rank": RANK,
        "tensor": name,
        "nnz": t.nnz,
        "launches": len(b.launches),
        "queues": queues,
        "block_budget_nnz": block,
        "backend": _jax_backend(),
        "note": ("One MTTKRP per memory tier (device-resident launch "
                 "cache / host-streamed lazy chunks / disk-streamed mmap "
                 "store), outputs equal to f32 rounding.  host_window_bytes is "
                 "the bounded padded-chunk window the streaming loop "
                 "holds (queues x reservation); ratio_vs_all_launches "
                 "is what the lazy-padding fix saves over the old eager "
                 "prepare_chunks.  On this CPU container the disk tier "
                 "reads from page cache; on a real deployment the mmap "
                 "page-ins overlap the H2D queue."),
        "store_file_bytes": file_bytes,
        "store_write_s": save_s,
        "format_bytes": nnz_bytes,
        "host_window_bytes": window,
        "all_launches_padded_bytes": all_padded,
        "host_window_ratio_vs_all_launches": window / all_padded,
        "us_per_call": {k: v * 1e6 for k, v in variants.items()},
        "gb_per_s": {k: nnz_bytes / v / 1e9 for k, v in variants.items()},
        "fraction_of_in_memory": {k: t_mem / v for k, v in variants.items()},
        "disk_stats": ds.snapshot(),
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
    return payload


def bench_obs(rows, *, fast: bool = False,
              json_path: str | None = "BENCH_6.json",
              trace_path: str | None = "TRACE_6.json") -> dict:
    """Observability cost + correctness (ISSUE 6).

    Two measurements:

    * **Traced disk-streamed CP-ALS**: runs a full disk-streamed CP-ALS
      sweep with span tracing ON, writes the Chrome trace JSON
      (``trace_path``; load it at https://ui.perfetto.dev), and
      cross-checks the per-track span duration sums against the plan's
      ``EngineStats`` totals — they must agree, because the hot loop
      records trace events from the *same* timestamps that feed the
      stats.
    * **Tracing overhead**: in-memory MTTKRP us_per_call with tracing
      disabled vs enabled.  The disabled path is the default everywhere
      else in the benchmark suite; its cost is one module-flag check per
      instrumentation site.
    """
    import shutil
    import tempfile
    from repro import obs
    from repro.core.cp_als import cp_als
    from repro.engine import plan_for

    name = "uber-like" if fast else "chicago-like"
    block = 1 << 11 if fast else 1 << 12
    sweeps = 2
    rank = 8 if fast else RANK
    t = core.paper_like(name, seed=0)
    b = core.build_blco(t, max_nnz_per_block=block)
    norm_x = float(np.linalg.norm(np.asarray(t.values, np.float64)))
    factors = _factors(t)
    own_dir = tempfile.mkdtemp()
    was_enabled = obs.is_enabled()
    try:
        path = f"{own_dir}/bench_obs.blco"
        # untimed warm-up sweep (tracing off): compile + page the store in
        warm = plan_for(b, 1 << 40, rank=rank, backend="disk_streamed",
                        store_path=path)
        cp_als(warm, t.dims, rank, iters=1, norm_x=norm_x, tol=0.0, seed=0)
        warm.close()

        obs.enable()
        obs.clear()
        plan = plan_for(b, 1 << 40, rank=rank, backend="disk_streamed",
                        store_path=path)
        t0 = time.perf_counter()
        cp_als(plan, t.dims, rank, iters=sweeps, norm_x=norm_x, tol=0.0,
               seed=0)
        traced_wall_s = time.perf_counter() - t0
        st = plan.stats()
        plan.close()
        obs.disable()

        totals = obs.track_totals()
        n_spans = len(obs.trace.spans())
        if trace_path:
            obs.write_chrome_trace(trace_path)
        obs.clear()

        # per-track span sums vs the EngineStats the same timestamps fed
        pairs = {
            "store": (totals.get("store", 0.0), st.disk_time_s),
            "h2d": (totals.get("h2d", 0.0), st.put_time_s),
            "dispatch": (totals.get("dispatch", 0.0), st.dispatch_time_s),
            "device": (totals.get("device", 0.0), st.device_time_s),
        }
        consistency = {
            track: abs(span_s - stat_s) / stat_s if stat_s > 0 else 0.0
            for track, (span_s, stat_s) in pairs.items()}
        max_rel_err = max(consistency.values())

        # tracing overhead on the in-memory hot path (flag check only when
        # disabled; span + ring-buffer append when enabled)
        mem = plan_for(b, 1 << 40, rank=rank, backend="in_memory")
        t_off = _time(lambda: mem.mttkrp(factors, 0))
        obs.enable()
        obs.clear()
        t_on = _time(lambda: mem.mttkrp(factors, 0))
        obs.disable()
        obs.clear()
        mem.close()
        overhead = t_on / t_off - 1.0
    finally:
        if was_enabled:
            obs.enable()
        shutil.rmtree(own_dir, ignore_errors=True)

    rows.append((f"bench6.{name}.traced_disk_als", traced_wall_s * 1e6,
                 f"{n_spans} spans, max track err {max_rel_err*100:.2f}%"))
    for track, (span_s, stat_s) in pairs.items():
        rows.append((f"bench6.{name}.track_{track}", span_s * 1e6,
                     f"stats={stat_s*1e6:.0f}us "
                     f"err={consistency[track]*100:.2f}%"))
    rows.append((f"bench6.{name}.tracing_overhead_in_memory", t_on * 1e6,
                 f"off={t_off*1e6:.0f}us ({overhead*100:+.2f}%)"))
    payload = {
        "bench": "observability_tracing",
        "fast_mode": fast,
        "rank": rank,
        "tensor": name,
        "nnz": t.nnz,
        "launches": len(b.launches),
        "sweeps": sweeps,
        "backend": _jax_backend(),
        "note": ("Traced disk-streamed CP-ALS: per-track span duration "
                 "sums vs EngineStats totals (identical timestamps, so "
                 "rel err ~0 by construction), plus in-memory MTTKRP "
                 "us_per_call with tracing enabled vs disabled.  The "
                 "enabled-overhead measurement is noisy at CPU-container "
                 "timescales; the acceptance bar (<2%) applies to the "
                 "DISABLED path vs an untraced build."),
        "spans_recorded": n_spans,
        "traced_wall_s": traced_wall_s,
        "track_span_s": {k: v[0] for k, v in pairs.items()},
        "stats_totals_s": {k: v[1] for k, v in pairs.items()},
        "track_rel_err": consistency,
        "max_track_rel_err": max_rel_err,
        "hist_counts": {
            "dispatch_s": st.hist.dispatch_s.count,
            "put_chunk_s": st.hist.put_chunk_s.count,
            "disk_read_s": st.hist.disk_read_s.count,
            "launch_nnz": st.hist.launch_nnz.count,
        },
        "in_memory_us_tracing_off": t_off * 1e6,
        "in_memory_us_tracing_on": t_on * 1e6,
        "tracing_enabled_overhead_frac": overhead,
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
    return payload


def _peak_bandwidths(fast: bool) -> dict:
    """Measured achievable ceilings per tier edge on THIS host.

    Microbenchmarks, not datasheet numbers: a fenced ``device_put`` of a
    large contiguous buffer (host_device), a fenced elementwise kernel
    over a device-resident buffer counting read+write traffic
    (device_hbm), and a scratch-file read (disk_host — on this CPU
    container that is page-cache speed, the same medium the mmap'd store
    chunks actually read from, so fractions stay apples-to-apples).
    """
    import shutil
    import tempfile

    import jax

    mb = 16 if fast else 64
    nbytes = mb << 20
    host_buf = np.ones(nbytes // 4, np.float32)

    def put():
        jax.device_put(host_buf).block_until_ready()

    t_h2d = _time(put, warmup=1, iters=3)

    dev = jax.device_put(host_buf)
    dev.block_until_ready()
    g = jax.jit(lambda a: a * 2.0)
    g(dev).block_until_ready()
    t_hbm = _time(lambda: g(dev).block_until_ready(), warmup=1, iters=3)

    own_dir = tempfile.mkdtemp()
    try:
        path = f"{own_dir}/scratch.bin"
        host_buf.tofile(path)

        def rd():
            np.fromfile(path, np.uint8)

        t_disk = _time(rd, warmup=1, iters=3)
    finally:
        shutil.rmtree(own_dir, ignore_errors=True)

    return {
        "disk_host": nbytes / t_disk / 1e9,
        "host_device": nbytes / t_h2d / 1e9,
        "device_hbm": 2 * nbytes / t_hbm / 1e9,   # read + write per element
    }


def _peak_flops() -> float:
    """Measured device flop ceiling: a fenced jitted matmul."""
    import jax
    import jax.numpy as jnp
    n = 512
    x = jnp.ones((n, n), jnp.float32)
    f = jax.jit(lambda a: a @ a)
    f(x).block_until_ready()
    t = _time(lambda: f(x).block_until_ready(), warmup=1, iters=3)
    return 2.0 * n ** 3 / t


def bench_roofline(rows, *, fast: bool = False,
                   json_path: str | None = "BENCH_7.json",
                   store_dir: str | None = None) -> dict:
    """Bandwidth ledger + roofline attribution (ISSUE 10).

    Re-measures the BENCH_5 workload (same tensor, block budget, queue
    depth) with the bandwidth ledger enabled, then:

    * **conservation** — per (regime, edge), ledger bytes/seconds must
      equal the plans' ``EngineStats`` counters with 0 relative error
      (the instrumentation records the identical locals; see
      ``repro.obs.ledger``);
    * **roofline** — measures this host's achievable ceiling per edge
      with microbenchmarks, then reports achieved GB/s and achieved
      fraction per edge per regime, naming each regime's saturated edge —
      turning BENCH_5's 0.80x/0.65x streaming gaps into a statement
      about *which* tier edge is the bottleneck;
    * **overhead** — in-memory MTTKRP us_per_call with tracing+ledger
      both enabled vs both disabled (the +2% acceptance bar).
    """
    import shutil
    import tempfile
    from repro import obs
    from repro.obs import ledger
    from repro.engine import plan_for
    from repro.store import DiskStreamedPlan, open_blco, save_blco

    name = "uber-like" if fast else "amazon-like"
    block = 1 << 11 if fast else 1 << 12
    iters = 2 if fast else 5
    warmup = 1 if fast else 2
    queues = 4
    t = core.paper_like(name, seed=0)
    b = core.build_blco(t, max_nnz_per_block=block)
    factors = _factors(t)
    mode = 0
    own_dir = tempfile.mkdtemp() if store_dir is None else None
    sdir = store_dir or own_dir
    os.makedirs(sdir, exist_ok=True)
    path = f"{sdir}/bench_roofline.blco"

    peaks = _peak_bandwidths(fast)
    peak_flops = _peak_flops()

    was_tracing = obs.is_enabled()
    was_ledger = ledger.is_enabled()
    mem = host = disk = None
    try:
        save_blco(b, path)

        # the ledger is on from plan construction (the in-memory upload is
        # part of its regime's host_device account) through every call the
        # timing loops make — stats and ledger see the same activity
        ledger.enable()
        ledger.clear()
        mem = plan_for(b, 1 << 40, rank=RANK, backend="in_memory")
        host = plan_for(b, 1 << 40, rank=RANK, backend="streamed",
                        queues=queues)
        disk = DiskStreamedPlan(open_blco(path), queues=queues)

        t_mem = _time(lambda: mem.mttkrp(factors, mode),
                      warmup=warmup, iters=iters)
        t_host = _time(lambda: host.mttkrp(factors, mode),
                       warmup=warmup, iters=iters)
        t_disk = _time(lambda: disk.mttkrp(factors, mode),
                       warmup=warmup, iters=iters)

        conservation = ledger.verify_conservation([
            ("in_memory", mem.stats()),
            ("streamed", host.stats()),
            ("disk_streamed", disk.stats()),
        ])
        report = obs.roofline_report(peaks=peaks, peak_flops=peak_flops)
        ledger.disable()

        # tracing + ledger enabled overhead on the in-memory hot path
        t_plain = _time(lambda: mem.mttkrp(factors, mode),
                        warmup=warmup, iters=iters)
        obs.enable()
        obs.clear()
        ledger.enable()
        t_obs = _time(lambda: mem.mttkrp(factors, mode),
                      warmup=warmup, iters=iters)
        obs.disable()
        obs.clear()
        ledger.disable()
        ledger.clear()
        overhead = t_obs / t_plain - 1.0
    finally:
        for plan in (mem, host, disk):
            if plan is not None:
                plan.close()
        if was_tracing:
            obs.enable()
        if was_ledger:
            ledger.enable()
        if own_dir is not None:
            shutil.rmtree(own_dir, ignore_errors=True)

    variants = {"in_memory": t_mem, "streamed": t_host,
                "disk_streamed": t_disk}
    achieved_fraction: dict[str, float] = {}
    saturated_edge: dict[str, str] = {}
    for regime, rep in report["regimes"].items():
        saturated_edge[regime] = rep["saturated_edge"]
        for edge, er in rep["edges"].items():
            frac = er.get("achieved_fraction")
            if frac is not None and er.get("seconds", 0.0) > 0.0:
                achieved_fraction[f"{regime}.{edge}"] = frac
                rows.append((f"bench7.{name}.{regime}.{edge}",
                             er["seconds"] * 1e6,
                             f"{er['gb_per_s']:.2f}GB/s "
                             f"({frac*100:.0f}% of {er['peak_gb_per_s']:.1f}"
                             f"GB/s peak)"))
    for regime, rep in report["regimes"].items():
        rows.append((f"bench7.{name}.{regime}.bound", 0.0,
                     f"{rep['bound']} (AI={rep['arithmetic_intensity']:.2f}"
                     f" flops/B, saturated: {saturated_edge[regime]})"))
    rows.append((f"bench7.{name}.conservation", 0.0,
                 f"max_edge_rel_err={conservation['max_rel_err']:.1e} "
                 f"({len(conservation['checks'])} checks)"))
    rows.append((f"bench7.{name}.obs_overhead_in_memory", t_obs * 1e6,
                 f"plain={t_plain*1e6:.0f}us ({overhead*100:+.2f}%)"))

    payload = {
        "bench": "bandwidth_roofline",
        "fast_mode": fast,
        "rank": RANK,
        "tensor": name,
        "nnz": t.nnz,
        "launches": len(b.launches),
        "queues": queues,
        "block_budget_nnz": block,
        "backend": _jax_backend(),
        "note": ("BENCH_5 workload re-measured under the bandwidth "
                 "ledger.  peaks are microbenchmarked achievable "
                 "ceilings on THIS host (disk_host is page-cache speed "
                 "on the CPU container — the same medium the mmap'd "
                 "store reads, so achieved fractions are "
                 "apples-to-apples; fractions can exceed 1.0 when "
                 "the workload's reads are cache-warmer than the "
                 "cold scratch-file microbenchmark).  "
                 "device_hbm bytes are "
                 "model-attributed per kernel (see "
                 "repro.obs.ledger.hbm_model_bytes); its seconds are "
                 "the fenced device spans.  max_edge_rel_err compares "
                 "ledger accounts against EngineStats counters and is "
                 "exactly 0.0 by construction.  saturated_edge names "
                 "the edge running closest to its ceiling per regime — "
                 "the direct input to the ROADMAP pipelining/compression "
                 "item."),
        "peak_gb_per_s": peaks,
        "peak_flops": peak_flops,
        "roofline": report,
        "achieved_fraction": achieved_fraction,
        "saturated_edge": saturated_edge,
        "bound": {r: rep["bound"] for r, rep in report["regimes"].items()},
        "max_edge_rel_err": conservation["max_rel_err"],
        "conservation_checks": len(conservation["checks"]),
        "us_per_call": {k: v * 1e6 for k, v in variants.items()},
        "in_memory_us_obs_off": t_plain * 1e6,
        "in_memory_us_obs_on": t_obs * 1e6,
        "obs_enabled_overhead_frac": overhead,
    }
    if json_path:
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
    return payload


def _jax_backend() -> str:
    import jax
    return jax.default_backend()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke mode: dispatch bench only, reduced "
                         "suite/iterations")
    ap.add_argument("--json", default="BENCH_3.json", metavar="PATH",
                    help="where to write the machine-readable dispatch "
                         "bench (default: BENCH_3.json; '' disables)")
    ap.add_argument("--mt-json", default="BENCH_4.json", metavar="PATH",
                    help="where to write the weighted multi-tenant service "
                         "bench (default: BENCH_4.json; '' disables)")
    ap.add_argument("--oom-json", default="BENCH_5.json", metavar="PATH",
                    help="where to write the memory-hierarchy (disk vs "
                         "host vs in-memory) bench (default: BENCH_5.json; "
                         "'' disables)")
    ap.add_argument("--store-dir", default=None, metavar="DIR",
                    help="persistent store directory for bench_oom "
                         "(default: a temp dir, removed afterwards)")
    ap.add_argument("--obs-json", default="BENCH_6.json", metavar="PATH",
                    help="where to write the observability bench "
                         "(default: BENCH_6.json; '' disables)")
    ap.add_argument("--trace-json", default="TRACE_6.json", metavar="PATH",
                    help="where to write the Chrome trace JSON of the "
                         "traced disk-streamed CP-ALS (default: "
                         "TRACE_6.json; '' disables)")
    ap.add_argument("--roofline-json", default="BENCH_7.json", metavar="PATH",
                    help="where to write the bandwidth-ledger / roofline "
                         "bench (default: BENCH_7.json; '' disables)")
    args = ap.parse_args(argv)
    from repro.core.compile_cache import enable_compile_cache
    enable_compile_cache()

    rows: list[tuple[str, float, str]] = []
    print("# BLCO paper benchmarks (CPU-scale analogues; see EXPERIMENTS.md)")
    if not args.fast:
        bench_fig8_fig9_fig1(rows)
        bench_table3(rows)
        bench_fig10(rows)
        bench_fig11_fig12(rows)
        bench_embed_grad(rows)
        bench_service(rows)
    bench_dispatch(rows, fast=args.fast, json_path=args.json or None)
    bench_multitenant(rows, fast=args.fast, json_path=args.mt_json or None)
    bench_oom(rows, fast=args.fast, json_path=args.oom_json or None,
              store_dir=args.store_dir)
    bench_obs(rows, fast=args.fast, json_path=args.obs_json or None,
              trace_path=args.trace_json or None)
    bench_roofline(rows, fast=args.fast,
                   json_path=args.roofline_json or None,
                   store_dir=args.store_dir)
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")


if __name__ == "__main__":
    main()
