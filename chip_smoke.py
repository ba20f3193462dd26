#!/usr/bin/env python3
"""Smoke run of the decomposition system's main path on TPU chips.

    python chip_smoke.py              # one chip: phases 1-6 below
    python chip_smoke.py --chips 4    # four chips: the sharded regime only

The tensor has NELL-2's published FROSTT shape (paper Table 2): 12,092 x
9,184 x 28,818 with 76,879,419 distinct nnz, float32 values, decomposed at
rank 32.  It is generated from ``--seed`` by ``core.frostt_like`` (a
power-law head over a uniform tail).  By default the nnz are cut to
``DEFAULT_NNZ`` and the dims kept, so that every phase fits the run's
1200 s limit with room to spare; ``--nnz 76879419`` runs the published
nnz.  Generation and BLCO construction are host set-up, timed apart from
the phases.

One chip, each phase a check that exits non-zero when it fails:
  1. device      JAX sees a TPU, else exit 1 before any work;
  2. in-memory   ``plan_for`` on the chip's memory (less a headroom) picks
                 ``in_memory`` with no demotion; MTTKRP in every mode
                 agrees with a float64 host reference; 5 CP-ALS sweeps;
  3. streamed    a budget below the in-memory need picks ``streamed``;
                 MTTKRP and 2 sweeps agree with phase 2;
  4. disk        a host budget below the tensor spills it to a store under
                 ``.smoke_run/`` and picks ``disk_streamed``; as phase 3;
  5. service     ``DecompositionService`` runs 4 jobs (NELL-2 at ranks 16
                 and 32, uber-like and chicago-like); all end DONE with
                 no demotion, and each job's fits match a solo ``cp_als``;
  6. pallas      the fused kernel does not compile for TPU, and
                 ``plan_for(kernel="pallas")`` raises there.

Four chips: ``plan_for`` under a ``(chips, 1)`` data x model mesh picks
``sharded``; MTTKRP in every mode and 3 CP-ALS sweeps agree with an
in-memory plan on the first chip.

Times printed here are smoke timings, not a benchmark.  The last line of
stdout is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
RUN_DIR = ROOT / ".smoke_run"
RANK = 32
# f32 agreement bound: max |x - ref| / max |ref| over the compared entries,
# and |fit - fit_ref| for CP-ALS fits
TOL = 1e-4
# device bytes left out of the budget for the runtime, compiled programs
# and transfers in flight
HEADROOM = 1 << 30
REF_COLS = (0, RANK // 2 - 3, RANK - 1)     # rank columns the host checks
SWEEPS = 5
# nnz of the default run: the one-chip phases cost about 16 s of wall time
# per million nnz on a v5e (20M nnz took 404.6 s, 133.5 s of it compiling),
# so the published 76.9M would leave too little of the 1200 s limit
DEFAULT_NNZ = 25_000_000


def check(ok: bool, what: str) -> None:
    if not ok:
        print(f"FAILED: {what}", flush=True)
        sys.exit(1)


def say(msg: str) -> None:
    print(msg, flush=True)


class Laps:
    """Wall time of consecutive phases (smoke timing)."""

    def __init__(self):
        self.t0 = time.perf_counter()

    def done(self, phase: str) -> None:
        now = time.perf_counter()
        say(f"{phase} passed in {now - self.t0:.1f} s")
        self.t0 = now


def rel_err(x, ref) -> float:
    x = np.asarray(x, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(x - ref)) / np.max(np.abs(ref)))


class CompileClock:
    """Backend compile (or persistent-cache load) time, from JAX's own
    monitoring events."""

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


def device_budget(dev) -> int:
    """The plan budget on ``dev``: its memory limit less the headroom."""
    return int(dev.memory_stats()["bytes_limit"]) - HEADROOM


def host_reference(t, factors, mode: int, cols, chunk: int = 1 << 23):
    """float64 MTTKRP of COO tensor ``t`` on rank columns ``cols``, chunked
    over nnz so host memory stays bounded."""
    f64 = [np.asarray(f, np.float64)[:, cols] for f in factors]
    out = np.zeros((t.dims[mode], len(cols)))
    for s in range(0, t.nnz, chunk):
        idx = t.indices[s:s + chunk]
        v = t.values[s:s + chunk].astype(np.float64)
        for j in range(len(cols)):
            w = v.copy()
            for m in range(t.order):
                if m != mode:
                    w *= f64[m][idx[:, m], j]
            out[:, j] += np.bincount(idx[:, mode], weights=w,
                                     minlength=t.dims[mode])
    return out


def make_nell2(core, args):
    from repro.core.tensor import FROSTT_SUITE
    dims, full, _ = FROSTT_SUITE["nell-2"]
    nnz = args.nnz
    if nnz != full:
        say(f"nnz cut: {nnz:,} of NELL-2's {full:,} (dims kept)")
    t0 = time.perf_counter()
    t = core.frostt_like("nell-2", seed=args.seed, nnz=nnz)
    t1 = time.perf_counter()
    b = core.build_blco(t)
    t2 = time.perf_counter()
    say(f"set-up (host): NELL-2 {dims} nnz={t.nnz:,} generated in "
        f"{t1 - t0:.1f} s, BLCO built in {t2 - t1:.1f} s: "
        f"{len(b.launches)} launches, {core.format_bytes(b) / 1e9:.2f} GB")
    return t, b


def factors_for(dims, rank, seed):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((d, rank)).astype(np.float32) for d in dims]


def mttkrp_all(plan, factors, order):
    import jax.numpy as jnp
    dev = [jnp.asarray(f) for f in factors]
    return [np.asarray(plan.mttkrp(dev, m)) for m in range(order)]


def timed_sweeps(core, plan, t, rank, sweeps, seed, norm_x, label):
    """``sweeps`` CP-ALS sweeps (``core.cp_als``'s init and step), each
    timed to its fit's host read-back."""
    state = core.cp_als_init(t.dims, rank, norm_x=norm_x, tol=0.0, seed=seed)
    for i in range(sweeps):
        t0 = time.perf_counter()
        core.cp_als_step(plan, state)
        say(f"  {label} sweep {i + 1}: fit {state.fits[-1]!r} "
            f"({time.perf_counter() - t0:.2f} s smoke timing)")
    check(all(np.isfinite(state.fits)), f"{label}: fits finite")
    return list(state.fits)


def compare_tier(label, plan, factors, order, want_m, want_fits, core, t,
                 seed, norm_x):
    got = mttkrp_all(plan, factors, order)
    for m in range(order):
        err = rel_err(got[m], want_m[m])
        say(f"  {label} mode {m}: max rel err vs in-memory {err:.2e}")
        check(err < TOL, f"{label} mode {m} MTTKRP within {TOL}")
    fits = timed_sweeps(core, plan, t, RANK, len(want_fits), seed, norm_x,
                        label)
    gap = max(abs(a - b) for a, b in zip(fits, want_fits))
    say(f"  {label}: fits within {gap:.2e} of in-memory")
    check(gap < TOL, f"{label} fits within {TOL} of in-memory")


def one_chip(args, jax, dev):
    from repro import core
    from repro.engine import factor_bytes, in_memory_bytes, plan_for
    from repro.service import (DecompositionService, SubmitDecomposition)

    t, b = make_nell2(core, args)
    norm_x = float(np.linalg.norm(t.values.astype(np.float64)))
    budget = device_budget(dev)
    order = t.order
    laps = Laps()

    # ---- phase 2: in-memory ------------------------------------------------
    say(f"phase 2 in-memory: budget {budget:,} B (the chip's bytes_limit "
        f"less {HEADROOM:,} B headroom)")
    t0 = time.perf_counter()
    plan = plan_for(b, budget, rank=RANK)
    say(f"  backend={plan.backend} demotions={plan.stats().demotions} "
        f"device_bytes={plan.device_bytes():,} "
        f"({time.perf_counter() - t0:.1f} s to plan and upload)")
    check(plan.backend == "in_memory", "phase 2 backend is in_memory")
    check(plan.stats().demotions == 0, "phase 2 has no demotion")
    factors = factors_for(t.dims, RANK, args.seed + 1)
    mem_m = mttkrp_all(plan, factors, order)
    for m in range(order):
        ref = host_reference(t, factors, m, REF_COLS)
        err = rel_err(mem_m[m][:, REF_COLS], ref)
        say(f"  mode {m}: max rel err vs float64 host reference {err:.2e} "
            f"(columns {REF_COLS})")
        check(err < TOL, f"phase 2 mode {m} MTTKRP within {TOL}")
    mem_fits = timed_sweeps(core, plan, t, RANK, SWEEPS, args.seed, norm_x,
                            "in-memory")
    # the solo runs the service's NELL-2 jobs are held to (phase 5)
    solo16 = timed_sweeps(core, plan, t, 16, 2, args.seed, norm_x,
                          "solo rank-16")
    plan.close()
    laps.done("phase 2")

    # ---- phase 3: host-streamed ---------------------------------------------
    need = in_memory_bytes(b, RANK) + factor_bytes(t.dims, RANK, np.float32)
    say(f"phase 3 host-streamed: budget {need - 1:,} B, one below the "
        f"in-memory need")
    plan = plan_for(b, need - 1, rank=RANK)
    say(f"  backend={plan.backend} demotions={plan.stats().demotions} "
        f"device_bytes={plan.device_bytes():,}")
    check(plan.backend == "streamed", "phase 3 backend is streamed")
    compare_tier("streamed", plan, factors, order, mem_m, mem_fits[:2], core,
                 t, args.seed, norm_x)
    plan.close()
    laps.done("phase 3")

    # ---- phase 4: disk-streamed ---------------------------------------------
    RUN_DIR.mkdir(exist_ok=True)
    store = RUN_DIR / "nell2.blco"
    host_budget = core.format_bytes(b) - 1
    say(f"phase 4 disk-streamed: host budget {host_budget:,} B, store "
        f"{store.relative_to(ROOT)}")
    t0 = time.perf_counter()
    plan = plan_for(b, budget, rank=RANK, host_budget_bytes=host_budget,
                    store_path=str(store))
    say(f"  backend={plan.backend} demotions={plan.stats().demotions} "
        f"store {store.stat().st_size / 1e9:.2f} GB written in "
        f"{time.perf_counter() - t0:.1f} s")
    check(plan.backend == "disk_streamed", "phase 4 backend is disk_streamed")
    compare_tier("disk-streamed", plan, factors, order, mem_m, mem_fits[:2],
                 core, t, args.seed, norm_x)
    plan.close()
    store.unlink()
    laps.done("phase 4")

    # ---- phase 5: service ---------------------------------------------------
    say(f"phase 5 service: 4 jobs, device budget {budget:,} B")
    small = {name: core.paper_like(name, seed=args.seed)
             for name in ("uber-like", "chicago-like")}
    svc = DecompositionService(device_budget_bytes=budget)
    jobs = {
        "nell-2/r16": (svc.submit(SubmitDecomposition(
            tensor=t, rank=16, iters=2, tol=0.0, seed=args.seed,
            tenant="A")), solo16),
        "nell-2/r32": (svc.submit(SubmitDecomposition(
            tensor=t, rank=RANK, iters=2, tol=0.0, seed=args.seed,
            tenant="B")), mem_fits[:2]),
    }
    for name, ts in small.items():
        sb = core.build_blco(ts)
        splan = plan_for(sb, budget, rank=8)
        solo = core.cp_als(splan, ts.dims, 8,
                           norm_x=float(np.linalg.norm(
                               ts.values.astype(np.float64))),
                           iters=5, tol=0.0, seed=args.seed).fits
        splan.close()
        jobs[name] = (svc.submit(SubmitDecomposition(
            tensor=ts, rank=8, iters=5, tol=0.0, seed=args.seed,
            tenant="C")), solo)
    t0 = time.perf_counter()
    svc.run()
    say(f"  service ran in {time.perf_counter() - t0:.1f} s")
    for name, (jid, solo) in jobs.items():
        st = svc.status(jid)
        say(f"  {name}: state={st.state} backend={st.backend} "
            f"iterations={st.iteration} fit={st.fit!r}")
        check(st.state == "done", f"service job {name} is DONE "
              f"(error: {st.error})")
        fits = svc.result(jid).result.fits
        gap = max(abs(a - b) for a, b in zip(fits, solo))
        check(len(fits) == len(solo) and gap < TOL,
              f"service job {name} fits within {TOL} of solo cp_als")
    demotions = svc.service_metrics()["demotions_total"]
    say(f"  demotions_total={demotions}")
    check(demotions == 0, "service has no demotion")
    laps.done("phase 5")

    # ---- phase 6: fused Pallas kernel --------------------------------------
    try:
        plan_for(b, budget, rank=RANK, kernel="pallas")
    except NotImplementedError as e:
        say(f"phase 6 pallas: fused kernel not supported on this chip: {e}")
    else:
        check(False, "plan_for(kernel='pallas') must raise on a TPU")


def four_chips(args, jax, devs):
    from jax.sharding import Mesh
    from repro import core
    from repro.dist.context import set_mesh
    from repro.engine import InMemoryPlan, plan_for

    t, b = make_nell2(core, args)
    norm_x = float(np.linalg.norm(t.values.astype(np.float64)))
    budget = sum(device_budget(d) for d in devs)
    mesh = Mesh(np.array(devs).reshape(len(devs), 1), ("data", "model"))
    say(f"sharded: mesh {dict(mesh.shape)} over {len(devs)} devices, "
        f"budget {budget:,} B")
    set_mesh(mesh)
    try:
        plan = plan_for(b, budget, rank=RANK)
    finally:
        set_mesh(None)
    check(plan.backend == "sharded", "backend is sharded")
    per_dev = plan.bytes_by_device()
    for d in devs:
        say(f"  device {d.id}: {per_dev.get(d, 0):,} B of nnz shards")
    check(all(per_dev.get(d, 0) > 0 for d in devs),
          "every device holds a shard")
    with jax.default_device(devs[0]):
        ref = InMemoryPlan(b)
        factors = factors_for(t.dims, RANK, args.seed + 1)
        want = mttkrp_all(ref, factors, t.order)
        want_fits = timed_sweeps(core, ref, t, RANK, 3, args.seed, norm_x,
                                 "single-chip")
    ref.close()
    compare_tier("sharded", plan, factors, t.order, want, want_fits, core, t,
                 args.seed, norm_x)
    plan.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every phase on one chip; 4: the sharded "
                         "regime only")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--nnz", type=int, default=DEFAULT_NNZ,
                    help="NELL-2's nnz, cut below the published 76,879,419 "
                         "(dims are kept)")
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repro sources under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    # ---- phase 1: device ----------------------------------------------------
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {devs[0].platform}",
              file=sys.stderr)
        return 1
    import jaxlib
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:                                   # noqa: BLE001
        libtpu = "not found"
    say(f"phase 1 device: {devs[0].device_kind} x{len(devs)} "
        f"(jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {libtpu})")
    check(len(devs) >= args.chips, f"{args.chips} chips")
    check(all(d.platform == "tpu" for d in devs), "every device is a TPU")

    from repro.core.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    clock = CompileClock(jax)
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(args, jax, devs[0])
    else:
        four_chips(args, jax, devs[:args.chips])
    say(f"compilation: {clock.seconds:.1f} s over {clock.programs} programs, "
        f"{clock.cache_hits} read from the persistent cache at {cache_dir}")
    say(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
