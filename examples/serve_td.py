"""Multi-tenant tensor-decomposition service demo — mixed execution regimes.

Four CP-ALS jobs from three tenants on two distinct tensors share one
device through the service layer, under ONE measured byte budget:

* the engine gives the small repeated tensor the **device-resident fast
  path** (one pooled DeviceBLCO copy, zero per-iteration H2D) while the
  larger tensor **streams** through pooled fixed reservations;
* the repeated tensor is a BLCO construction-cache hit (one shared copy)
  AND a residency-pool hit (its second tenant only pays its per-job factor
  working set);
* admission charges exactly ``plan.device_bytes()`` — the pooled tensor
  state once, plus each job's private factor working set;
* results are bit-identical to a solo run through the same engine regime.

A second act drives the **async runtime**: the same workload submitted to
``ServiceRuntime`` with per-tenant weights (tenant A at weight 2 gets twice
the sweeps), a streamed status feed, and a mid-run cancellation that
measurably frees pooled bytes.

A third act exercises the **persistent store**: the service is killed
mid-decomposition, restarted from its snapshot + spill store, and the job
resumes from its checkpointed ``CPState`` — disk-streaming the tensor
straight off the store, with no BLCO rebuild and the same fit trajectory
to f32 rounding.

    PYTHONPATH=src python examples/serve_td.py
"""
import os
import shutil
import tempfile

import numpy as np

from repro import core
from repro.core.launches import launch_cache_bytes
from repro.engine import factor_bytes, plan_for
from repro.service import (BuildParams, CancelJob, DecompositionService,
                           ServiceRuntime, SubmitDecomposition)

build = BuildParams(max_nnz_per_block=1 << 12)   # small blocks -> real streaming
t_uber = core.paper_like("uber-like", seed=0)
t_chicago = core.paper_like("chicago-like", seed=0)
t_uber_again = core.paper_like("uber-like", seed=0)   # same content, new object

# size the budget so uber fits device-resident but chicago must stream:
# uber's resident copy + a working set per job + one pooled reservation
# set for chicago, with headroom well below chicago's residency cost
b_uber = core.build_blco(t_uber, max_nnz_per_block=1 << 12)
b_chicago = core.build_blco(t_chicago, max_nnz_per_block=1 << 12)
from repro.core.streaming import reservation_for

chicago_stream = reservation_for(b_chicago).bytes_in_flight(4)
fb_uber = factor_bytes(t_uber.dims, 8, np.float32)
fb_ch16 = factor_bytes(t_chicago.dims, 16, np.float32)
fb_ch8 = factor_bytes(t_chicago.dims, 8, np.float32)
budget = launch_cache_bytes(b_uber) + 2 * fb_uber \
    + chicago_stream + fb_ch16 + fb_ch8 + (32 << 10)
# chicago can never go resident: when its first job is admitted (tenantA's
# uber copy + working set already held), the remaining budget is below
# chicago's residency cost + its working set
assert budget - launch_cache_bytes(b_uber) - fb_uber \
    < launch_cache_bytes(b_chicago) + fb_ch16

svc = DecompositionService(device_budget_bytes=budget, queues=4)
jobs = {
    "tenantA/uber":     svc.submit(SubmitDecomposition(
        tensor=t_uber, rank=8, iters=6, seed=1, build=build,
        tenant="tenantA")),
    "tenantB/chicago":  svc.submit(SubmitDecomposition(
        tensor=t_chicago, rank=16, iters=6, seed=2, build=build,
        tenant="tenantB")),
    "tenantC/uber":     svc.submit(SubmitDecomposition(
        tensor=t_uber_again, rank=8, iters=6, seed=1, build=build,
        tenant="tenantC")),
    "tenantB/chicago8": svc.submit(SubmitDecomposition(
        tensor=t_chicago, rank=8, iters=6, seed=3, build=build,
        tenant="tenantB")),
}
print(f"submitted {len(jobs)} jobs on 2 distinct tensors "
      f"(budget {budget/1e6:.1f} MB, {svc.engine.queues} queues)")

results = svc.run()
m = svc.service_metrics()

for name, jid in jobs.items():
    st = svc.status(jid)
    r = results[jid]
    print(f"  {name:18s} job={jid} {st.state} backend={st.backend:9s} "
          f"iters={st.iteration} fit={st.fit:.4f} cache_hit={st.cache_hit} "
          f"h2d={r.metrics['h2d_bytes']/1e6:.1f}MB "
          f"launches={r.metrics['launches']}")

backends = {name: svc.status(jid).backend for name, jid in jobs.items()}
assert backends["tenantA/uber"] == "in_memory"       # fast path
assert backends["tenantC/uber"] == "in_memory"       # pooled residency
assert backends["tenantB/chicago"] == "streamed"     # too big -> streams
assert backends["tenantB/chicago8"] == "streamed"

print(f"service: {m['blco_cache_hits']} cache hit(s) / "
      f"{m['blco_cache_misses']} build(s); "
      f"measured admission peak {m['peak_admitted_reservation_bytes']/1e6:.2f}MB "
      f"<= budget; {m['iterations_total']} iterations "
      f"({m['iterations_per_sec']:.2f}/s); "
      f"{m['h2d_bytes_total']/1e6:.1f}MB H2D total")
assert m["peak_admitted_reservation_bytes"] <= budget
assert m["blco_cache_hits"] == 2       # repeated uber content + reused chicago
assert m["blco_cache_misses"] == 2     # one build per distinct tensor

# the multi-tenant result is exactly the solo result through the same regime
jid = jobs["tenantA/uber"]
solo_plan = plan_for(b_uber, 1 << 40, rank=8, backend="in_memory")
solo = core.cp_als(solo_plan, t_uber.dims, 8,
                   norm_x=float(np.linalg.norm(t_uber.values)),
                   iters=6, seed=1)
for a, b_ in zip(results[jid].result.factors, solo.factors):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                               rtol=1e-5, atol=1e-6)
solo_plan.close()
print("multi-tenant factors == solo engine factors (same seeds): OK")

# ---------------------------------------------------------------------------
# Act 2: the async runtime — weighted fair share, streaming status, cancel.
# ---------------------------------------------------------------------------
print("\n== async runtime (weighted shares + streaming + cancellation) ==")
# three uber tenants (3 working sets) + one streaming chicago tenant
budget2 = launch_cache_bytes(b_uber) + 3 * fb_uber \
    + chicago_stream + fb_ch16 + (32 << 10)
assert chicago_stream + fb_ch16 + (32 << 10) \
    < launch_cache_bytes(b_chicago) + fb_ch16
with ServiceRuntime(device_budget_bytes=budget2, queues=4) as rt:
    feed = rt.subscribe()                       # all-jobs status stream
    ja = rt.submit(SubmitDecomposition(tensor=t_uber, rank=8, iters=8,
                                       tol=0.0, seed=1, build=build,
                                       tenant="tenantA", weight=2.0))
    jb = rt.submit(SubmitDecomposition(tensor=t_uber, rank=8, iters=4,
                                       tol=0.0, seed=2, build=build,
                                       tenant="tenantB", weight=1.0))
    jc = rt.submit(SubmitDecomposition(tensor=t_uber, rank=8, iters=4,
                                       tol=0.0, seed=3, build=build,
                                       tenant="tenantC", weight=1.0))
    victim = rt.submit(SubmitDecomposition(tensor=t_chicago, rank=16,
                                           iters=10_000, tol=0.0, seed=4,
                                           build=build, tenant="tenantD"))
    first = feed.get(timeout=120)
    print(f"  first streamed event: job={first.job_id} kind={first.kind} "
          f"tenant={first.tenant}")
    assert rt.status(victim).state == "running"   # admitted as streamed
    held = rt.service.engine.pooled_bytes()
    res = rt.cancel(CancelJob(job_id=victim))
    print(f"  cancelled tenantD mid-run: freed {res.freed_bytes/1e6:.2f}MB "
          f"(pooled {held/1e6:.2f}MB -> "
          f"{rt.service.engine.pooled_bytes()/1e6:.2f}MB)")
    assert res.cancelled and res.freed_bytes > 0
    rt.drain(timeout=600)
    rt.unsubscribe(feed)
    mt = rt.service_metrics()
print(f"  tenant iterations: {mt['tenant_iterations']} "
      f"(weights A=2, B=C=1); cancellations={mt['jobs_cancelled']}")
assert mt["tenant_iterations"]["tenantA"] == 8
assert mt["tenant_iterations"]["tenantB"] == 4
assert mt["tenant_iterations"]["tenantC"] == 4
assert mt["jobs_cancelled"] == 1
print("async runtime: weighted shares + measured cancellation: OK")

# ---------------------------------------------------------------------------
# Act 3: kill the service mid-decomposition, restart from the persisted
# store, and watch the job resume from its checkpointed CPState.
# ---------------------------------------------------------------------------
print("\n== persistent store (kill -> restart -> resume) ==")
workdir = tempfile.mkdtemp()
store_dir = os.path.join(workdir, "store")
snap_dir = os.path.join(workdir, "snapshot")
ITERS = 10

# the uninterrupted trajectory we must reproduce across the restart
ref = DecompositionService(device_budget_bytes=budget, store_dir=store_dir)
ref_job = ref.submit(SubmitDecomposition(tensor=t_uber, rank=8, iters=ITERS,
                                         tol=0.0, seed=7, build=build,
                                         tenant="tenantA"))
ref.run()
ref_fits = ref.result(ref_job).result.fits

rt = ServiceRuntime(device_budget_bytes=budget, store_dir=store_dir).start()
job = rt.submit(SubmitDecomposition(tensor=t_uber, rank=8, iters=ITERS,
                                    tol=0.0, seed=7, build=build,
                                    tenant="tenantA"))
feed = rt.subscribe(job)
while True:                                     # let it make real progress
    ev = feed.get(timeout=120)
    if ev.kind == "iteration" and ev.iteration >= 3:
        break
rt.unsubscribe(feed)
rt.stop()            # "kill": the worker halts after its in-flight sweep
manifest = rt.snapshot(snap_dir)                # checkpoint at a sweep edge
assert manifest["jobs"], "job finished before the snapshot window"
ckpt_iter = manifest["jobs"][0]["iteration"]
del rt
print(f"  killed mid-run at iteration {ckpt_iter}/{ITERS} "
      f"(snapshot: {len(manifest['jobs'])} job, "
      f"{len(manifest['tensors'])} tensor in store)")

rt2 = ServiceRuntime.restore(snap_dir, device_budget_bytes=budget,
                             store_dir=store_dir)
st = rt2.status(job)                            # original job id survives
assert st.state == "running" and st.iteration == ckpt_iter
assert rt2.service.registry.misses == 0         # adopted off disk, no rebuild
with rt2:
    final = rt2.wait(job, timeout=600)
    fits = rt2.result(job).result.fits
    m3 = rt2.service_metrics()
print(f"  restored under job id {job}: resumed at iter {ckpt_iter}, "
      f"finished at iter {final.iteration} backend={final.backend}")
assert final.state == "done" and final.iteration == ITERS
assert final.backend == "disk_streamed"         # streams straight off the store
# trajectory preserved to f32 rounding: the resumed job disk-streams where
# the uninterrupted one ran device-resident, and the two compiled MTTKRPs
# may associate their sums differently
np.testing.assert_allclose(fits, ref_fits, rtol=0, atol=1e-5)
assert m3["jobs_restored"] == 1 and m3["blco_cache_misses"] == 0
print(f"  resumed fit trajectory == uninterrupted run ({len(fits)} sweeps, "
      f"to 1e-5); disk-streamed {m3['h2d_bytes_total']/1e6:.1f}MB from the store")
shutil.rmtree(workdir)
print("persistent store: kill -> restart -> resume: OK")
