"""Service persistence: snapshot mid-run, restore after a simulated
process restart, exact CP-ALS resumption, disk-streamed re-admission."""
import os

import numpy as np
import pytest

from repro import core
from repro.service import (BuildParams, DecompositionService, ServiceRuntime,
                           SubmitDecomposition)
from repro.store import StoreError, restore_service, snapshot_service

BUILD = BuildParams(max_nnz_per_block=1 << 10)
BUDGET = 64 << 20


def _tensor(seed=0):
    return core.paper_like("uber-like", seed=seed)


def _submit(svc, t, *, iters=6, seed=1, tenant="acme", weight=2.0, rank=8):
    return svc.submit(SubmitDecomposition(
        tensor=t, rank=rank, iters=iters, tol=0.0, seed=seed, build=BUILD,
        tenant=tenant, weight=weight))


def test_snapshot_requires_store_dir(tmp_path):
    svc = DecompositionService(device_budget_bytes=BUDGET)
    _submit(svc, _tensor())
    with pytest.raises(StoreError, match="store_dir"):
        svc.snapshot(str(tmp_path / "snap"))


def test_snapshot_restore_resumes_exactly(tmp_path):
    """Acceptance: kill the service mid-decomposition, restore from the
    persisted store, and the resumed fit trajectory equals the
    uninterrupted one — without rebuilding any BLCO.  The resumed job
    disk-streams where the uninterrupted one ran device-resident, and the
    two compiled MTTKRPs may associate their f32 sums differently, so the
    fits agree to 1e-5 (observed: 6e-8), not bit for bit."""
    store = str(tmp_path / "store")
    snap = str(tmp_path / "snap")
    t = _tensor()

    ref = DecompositionService(device_budget_bytes=BUDGET, store_dir=store)
    ref_job = _submit(ref, t)
    ref.run()
    ref_fits = ref.result(ref_job).result.fits

    svc = DecompositionService(device_budget_bytes=BUDGET, store_dir=store)
    jid = _submit(svc, t)
    for _ in range(3):
        svc.step()
    manifest = svc.snapshot(snap)
    assert [j["job_id"] for j in manifest["jobs"]] == [jid]
    assert manifest["jobs"][0]["iteration"] == 3
    key = manifest["jobs"][0]["tensor_key"]
    assert os.path.exists(manifest["tensors"][key]["file"])
    del svc                                   # simulated process death

    svc2 = DecompositionService.restore(snap, device_budget_bytes=BUDGET,
                                        store_dir=store)
    st = svc2.status(jid)                     # original id survives
    assert st.state == "running" and st.iteration == 3
    assert st.tenant == "acme" and st.weight == 2.0
    assert svc2.registry.misses == 0          # adopted from store, no rebuild
    assert st.backend == "disk_streamed"      # stub handle streams from disk
    svc2.run()
    fits = svc2.result(jid).result.fits
    np.testing.assert_allclose(fits, ref_fits, rtol=0, atol=1e-5)
    m = svc2.service_metrics()
    assert m["jobs_restored"] == 1
    assert m["disk_bytes_total"] > 0          # store->host traffic rolled up


def test_snapshot_keeps_terminal_jobs_and_queued(tmp_path):
    """DONE jobs persist as finished records: a restarted service keeps
    serving their status()/result() while never re-admitting them."""
    store = str(tmp_path / "store")
    snap = str(tmp_path / "snap")
    svc = DecompositionService(device_budget_bytes=BUDGET, store_dir=store,
                               max_active=1)
    done = _submit(svc, _tensor(), iters=1)
    while svc.status(done).state == "running":
        svc.step()
    running = _submit(svc, _tensor(), iters=5, seed=2)
    queued = _submit(svc, _tensor(seed=1), iters=5, seed=3)
    svc.step()
    assert svc.status(done).state == "done"
    assert svc.status(running).state == "running"
    assert svc.status(queued).state == "queued"
    done_factors = np.asarray(svc.result(done).result.factors[0])
    manifest = svc.snapshot(snap)
    snap_ids = {j["job_id"] for j in manifest["jobs"]}
    assert snap_ids == {done, running, queued}

    svc2 = DecompositionService.restore(snap, device_budget_bytes=BUDGET,
                                        store_dir=store)
    assert set(svc2.scheduler.jobs) == {done, running, queued}
    # the terminal record restores finished — status/result served, never
    # re-admitted (it is in no queue), factors bit-identical
    assert svc2.status(done).state == "done"
    assert done not in svc2.scheduler.pending
    assert done not in svc2.scheduler.active
    assert np.array_equal(
        np.asarray(svc2.result(done).result.factors[0]), done_factors)
    # a queued job was never admitted: it restores without a CPState and
    # initializes from its seed on admission
    svc2.run()
    assert svc2.status(running).state == "done"
    assert svc2.status(queued).state == "done"
    # new submissions continue past the restored ids
    new = _submit(svc2, _tensor(seed=2), iters=1)
    assert new > max(snap_ids)


def test_restore_missing_manifest_raises(tmp_path):
    svc = DecompositionService(device_budget_bytes=BUDGET)
    with pytest.raises(StoreError, match="manifest"):
        restore_service(str(tmp_path / "nope"), svc)


def test_runtime_snapshot_restore_mid_flight(tmp_path):
    """Satellite 6's machinery: ServiceRuntime.snapshot() at a quantum
    boundary, runtime restart, job resumes and completes."""
    store = str(tmp_path / "store")
    snap = str(tmp_path / "snap")
    t = _tensor()
    with ServiceRuntime(device_budget_bytes=BUDGET, store_dir=store) as rt:
        jid = rt.submit(SubmitDecomposition(
            tensor=t, rank=8, iters=50, tol=0.0, seed=1, build=BUILD,
            tenant="acme"))
        feed = rt.subscribe(jid)
        while True:                      # wait until real progress was made
            ev = feed.get(timeout=120)
            assert ev is not None
            if ev.kind == "iteration" and ev.iteration >= 2:
                prefix = list(ev.fits)   # trajectory the first process saw
                break
        rt.unsubscribe(feed)
        manifest = rt.snapshot(snap)
    # context exit stopped the runtime mid-decomposition ("kill")
    [rec] = manifest["jobs"]
    assert rec["state"] == "running" and rec["iteration"] >= 2

    rt2 = ServiceRuntime.restore(snap, device_budget_bytes=BUDGET,
                                 store_dir=store)
    with rt2:
        status = rt2.wait(jid, timeout=600)
    assert status.state == "done"
    assert status.iteration == 50
    assert rt2.service.registry.misses == 0   # no BLCO rebuild after restart
    fits = rt2.result(jid).result.fits
    assert len(fits) == 50
    # the checkpointed prefix is exactly what the first process computed
    # (the worker may have swept past the observed event before snapshot)
    k = min(len(prefix), rec["iteration"])
    assert k >= 2 and fits[:k] == prefix[:k]


def test_snapshot_is_nonintrusive(tmp_path):
    """Snapshotting persists tensors but never drops host copies or
    perturbs the running decomposition."""
    store = str(tmp_path / "store")
    snap = str(tmp_path / "snap")
    t = _tensor()
    ref = DecompositionService(device_budget_bytes=BUDGET, store_dir=store)
    rj = _submit(ref, t)
    ref.run()
    ref_fits = ref.result(rj).result.fits

    svc = DecompositionService(device_budget_bytes=BUDGET, store_dir=store)
    jid = _submit(svc, t)
    svc.step()
    handle = svc.scheduler.jobs[jid].handle
    was_resident = handle.resident
    svc.snapshot(snap)
    assert handle.resident == was_resident    # persist() keeps host copies
    svc.run()
    assert svc.result(jid).result.fits == ref_fits
