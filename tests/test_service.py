"""Multi-tenant decomposition service: registry cache, admission, fair share.

The acceptance scenario: >=3 concurrent jobs on >=2 distinct tensors run
through the scheduler with (a) a BLCO cache hit on the repeated tensor,
(b) admitted plan bytes never exceeding the budget, (c) per-job CP factors
matching a solo engine run on the same seeds.  Admission is by *measured*
``plan.device_bytes()``: small tensors get the device-resident fast path,
larger ones stream through pooled reservations, under one shared budget.
"""
import numpy as np
import pytest

from repro import core
from repro.engine import factor_bytes, in_memory_bytes, plan_for
from repro.service import (BuildParams, DecompositionService, MTTKRPQuery,
                           SubmitDecomposition, TensorRegistry)

BUILD = BuildParams(max_nnz_per_block=256)      # force many launches

def _t1(seed=6):
    return core.random_tensor((30, 22, 14), 1500, seed=seed, dist="powerlaw")


def _t2():
    return core.random_tensor((40, 25, 30), 2000, seed=3, dist="powerlaw")


def _norm(t):
    return float(np.linalg.norm(t.values))


def test_acceptance_three_jobs_two_tensors():
    svc = DecompositionService(device_budget_bytes=64 << 20, queues=3)
    t1, t2, t1_again = _t1(), _t2(), _t1()
    assert t1_again is not t1                     # distinct objects, same content
    j1 = svc.submit(SubmitDecomposition(tensor=t1, rank=6, iters=5, seed=7,
                                        build=BUILD))
    j2 = svc.submit(SubmitDecomposition(tensor=t2, rank=8, iters=5, seed=1,
                                        build=BUILD))
    j3 = svc.submit(SubmitDecomposition(tensor=t1_again, rank=6, iters=5,
                                        seed=7, build=BUILD))
    results = svc.run()
    assert set(results) == {j1, j2, j3}
    m = svc.service_metrics()
    # (a) BLCO cache hit on the repeated tensor
    assert m["blco_cache_hits"] == 1 and m["blco_cache_misses"] == 2
    assert svc.status(j3).cache_hit and not svc.status(j1).cache_hit
    # (b) admitted plan bytes never exceeded the budget; a 64 MiB budget
    # gives every tenant the device-resident fast path
    assert 0 < m["peak_admitted_reservation_bytes"] <= 64 << 20
    assert m["admitted_reservation_bytes"] == 0   # all released at the end
    assert all(svc.status(j).backend == "in_memory" for j in (j1, j2, j3))
    # (c) per-job factors match a solo engine run on the same seeds
    for jid, t, rank, seed in ((j1, t1, 6, 7), (j2, t2, 8, 1)):
        b = core.build_blco(t, max_nnz_per_block=256)
        plan = plan_for(b, 64 << 20, rank=rank, backend="in_memory")
        ref = core.cp_als(plan, t.dims, rank, norm_x=_norm(t), iters=5,
                          seed=seed)
        plan.close()
        got = results[jid].result
        np.testing.assert_allclose(got.fits, ref.fits, rtol=1e-5, atol=1e-6)
        for a, b_ in zip(got.factors, ref.factors):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=1e-4, atol=1e-5)
    # identical submissions produce identical factors (shared BLCO copy)
    for a, b_ in zip(results[j1].result.factors, results[j3].result.factors):
        assert np.array_equal(np.asarray(a), np.asarray(b_))


def test_round_robin_iteration_fair_share():
    svc = DecompositionService(device_budget_bytes=64 << 20, queues=2)
    ids = [svc.submit(SubmitDecomposition(tensor=_t1(), rank=4, iters=4,
                                          seed=s, tol=0.0, build=BUILD))
           for s in range(3)]
    svc.run()
    trace = svc.scheduler.trace
    assert len(trace) == 12                       # 3 jobs x 4 iterations
    # every scheduling cycle advances each active job exactly once
    for cycle in range(4):
        assert trace[cycle * 3:(cycle + 1) * 3] == ids


def test_fast_path_and_streaming_share_one_budget():
    """The ISSUE acceptance: under ONE budget the engine runs the small
    tensor device-resident and streams the large one, and admission charges
    exactly the measured plan bytes."""
    t_small, t_big = _t1(), _t2()
    probe = TensorRegistry()
    h_small = probe.register(t_small, build=BUILD)
    h_big = probe.register(t_big, build=BUILD)
    # budget: small's residency + big's reservation + both factor sets
    budget = h_small.in_memory_bytes \
        + factor_bytes(t_small.dims, 4, np.float32) \
        + h_big.spec.bytes_in_flight(2) \
        + factor_bytes(t_big.dims, 4, np.float32)
    assert budget - h_small.in_memory_bytes < h_big.in_memory_bytes

    svc = DecompositionService(device_budget_bytes=budget, queues=2)
    js = svc.submit(SubmitDecomposition(tensor=t_small, rank=4, iters=3,
                                        seed=0, build=BUILD))
    jb = svc.submit(SubmitDecomposition(tensor=t_big, rank=4, iters=3,
                                        seed=0, build=BUILD))
    assert svc.status(js).backend == "in_memory"      # fast path
    assert svc.status(jb).backend == "streamed"       # too big -> streams
    # measured admission: the resident copy + the pooled reservation + each
    # job's (never pooled) factor working set
    m = svc.service_metrics()
    assert m["admitted_reservation_bytes"] == \
        h_small.in_memory_bytes + factor_bytes(t_small.dims, 4, np.float32) \
        + h_big.spec.bytes_in_flight(2) \
        + factor_bytes(t_big.dims, 4, np.float32)
    svc.run()
    m = svc.service_metrics()
    assert svc.status(js).state == "done" and svc.status(jb).state == "done"
    assert m["peak_admitted_reservation_bytes"] <= budget
    assert m["admitted_reservation_bytes"] == 0
    # launches count compute dispatches: the resident job issues exactly
    # ONE fused dispatch per MTTKRP call (the launch-cache scan), while the
    # streamed job pays one dispatch per reservation chunk per call
    rs, rb = svc.result(js).metrics, svc.result(jb).metrics
    assert rs["backend"] == "in_memory" and \
        rs["launches"] == rs["mttkrp_calls"] > 0
    assert rb["backend"] == "streamed" and \
        rb["launches"] > rb["mttkrp_calls"] > 0
    # both still match a solo engine run on the same seeds
    b = core.build_blco(t_big, max_nnz_per_block=256)
    solo = plan_for(b, h_big.spec.bytes_in_flight(2)
                    + h_big.spec.temp_bytes(4, 4)
                    + factor_bytes(t_big.dims, 4, np.float32),
                    rank=4, queues=2)
    assert solo.backend == "streamed"
    ref = core.cp_als(solo, t_big.dims, 4, norm_x=_norm(t_big), iters=3,
                      seed=0)
    solo.close()
    np.testing.assert_allclose(svc.result(jb).result.fits, ref.fits,
                               rtol=1e-5, atol=1e-6)


def test_admission_control_respects_budget():
    # the budget fits the small tensor's regime but not the big one's ->
    # the second job must queue until the first completes and releases
    t1, t2 = _t1(), _t2()
    probe = TensorRegistry()
    small = probe.register(t1, build=BUILD).spec.bytes_in_flight(2)
    big = probe.register(
        t2, build=BuildParams(max_nnz_per_block=512)).spec.bytes_in_flight(2)
    assert small < big
    budget = big + factor_bytes(t2.dims, 4, np.float32)
    svc = DecompositionService(device_budget_bytes=budget, queues=2)
    j1 = svc.submit(SubmitDecomposition(tensor=t1, rank=4, iters=3, seed=0,
                                        build=BUILD))
    j2 = svc.submit(SubmitDecomposition(
        tensor=t2, rank=4, iters=3, seed=0,
        build=BuildParams(max_nnz_per_block=512)))
    assert svc.status(j1).state == "running"
    assert svc.status(j2).state == "queued"       # over budget: must wait
    assert svc.status(j2).queue_wait_s >= 0.0
    svc.run()
    m = svc.service_metrics()
    assert svc.status(j1).state == "done" and svc.status(j2).state == "done"
    assert m["peak_admitted_reservation_bytes"] <= budget


def test_tenants_share_pooled_state():
    """Plans over one pool entry charge the budget once, whichever pool.

    Same-content tensors under a big budget share ONE device-resident copy;
    under a tight budget, same-shape tensors share ONE reservation.  Each
    job's factor working set is charged per job on top of the pooled entry
    (it is private to the job, never shared)."""
    # residency pooling: 3 tenants, one DeviceBLCO copy, charged once
    svc = DecompositionService(device_budget_bytes=64 << 20, queues=2)
    for s in range(3):                            # same tensor content 3x
        svc.submit(SubmitDecomposition(tensor=_t1(), rank=4, iters=2, seed=s,
                                       build=BUILD))
    assert svc.engine.resident_count == 1         # one pooled resident copy
    assert svc.engine.pool_size == 0              # nothing streams
    one = svc.scheduler.jobs[0].handle.in_memory_bytes
    fb = factor_bytes(svc.scheduler.jobs[0].handle.dims, 4, np.float32)
    assert svc.service_metrics()["admitted_reservation_bytes"] == one + 3 * fb
    svc.run()
    assert svc.service_metrics()["peak_admitted_reservation_bytes"] == \
        one + 3 * fb
    assert svc.engine.resident_count == 0         # released at the end

    # reservation pooling: budget below residency -> all three stream
    # through one pooled shape, charged once (+ one working set per job)
    probe = TensorRegistry()
    h = probe.register(_t1(), build=BUILD)
    res_bytes = h.spec.bytes_in_flight(2)
    budget = res_bytes + 3 * fb + 1024
    assert budget < h.in_memory_bytes + fb        # residency can't fit
    svc = DecompositionService(device_budget_bytes=budget, queues=2)
    for s in range(3):
        svc.submit(SubmitDecomposition(tensor=_t1(), rank=4, iters=2, seed=s,
                                       build=BUILD))
    assert svc.engine.pool_size == 1              # one pooled shape
    assert svc.engine.resident_count == 0
    assert svc.service_metrics()["admitted_reservation_bytes"] == \
        res_bytes + 3 * fb
    svc.run()
    assert svc.service_metrics()["peak_admitted_reservation_bytes"] == \
        res_bytes + 3 * fb


def test_admission_charges_working_set_no_overcommit():
    """ISSUE 4 satellite: K admitted same-tensor jobs hold exactly
    K * factor_bytes + ONE pooled tensor copy.

    The old ``try_plan`` checked the factor working set at admission but
    never charged it to the ledger, so every later same-tensor job passed a
    check that assumed ``working`` was free — the budget could be
    overcommitted by N x factor_bytes.  This test fails on that code: all
    three jobs were admitted against a budget sized for two."""
    t = _t1()
    probe = TensorRegistry()
    h = probe.register(t, build=BUILD)
    fb = factor_bytes(h.dims, 4, np.float32)
    budget = h.in_memory_bytes + 2 * fb       # one copy + TWO working sets
    svc = DecompositionService(device_budget_bytes=budget, queues=2)
    ids = [svc.submit(SubmitDecomposition(tensor=t, rank=4, iters=2, seed=s,
                                          tol=0.0, build=BUILD))
           for s in range(3)]
    states = [svc.status(j).state for j in ids]
    assert states == ["running", "running", "queued"]
    m = svc.service_metrics()
    assert m["admitted_reservation_bytes"] == h.in_memory_bytes + 2 * fb
    assert m["admitted_reservation_bytes"] <= budget  # ledger == reality
    svc.run()
    assert all(svc.status(j).state == "done" for j in ids)
    assert svc.service_metrics()["peak_admitted_reservation_bytes"] <= budget
    assert svc.service_metrics()["admitted_reservation_bytes"] == 0


def test_pool_join_branch_checks_working_set():
    """ISSUE 4 satellite: the resident pool-join branch (resident cost 0)
    must still check AND charge the joiner's working set — the old code
    admitted any sharer of a pooled copy unconditionally."""
    t = _t1()
    probe = TensorRegistry()
    h = probe.register(t, build=BUILD)
    fb = factor_bytes(h.dims, 4, np.float32)
    budget = h.in_memory_bytes + fb           # exactly ONE job fits
    svc = DecompositionService(device_budget_bytes=budget, queues=2)
    j0 = svc.submit(SubmitDecomposition(tensor=t, rank=4, iters=2, seed=0,
                                        tol=0.0, build=BUILD))
    j1 = svc.submit(SubmitDecomposition(tensor=t, rank=4, iters=2, seed=1,
                                        tol=0.0, build=BUILD))
    assert svc.status(j0).state == "running"
    assert svc.status(j1).state == "queued"   # joining is NOT free
    assert svc.service_metrics()["admitted_reservation_bytes"] == budget
    svc.run()
    assert svc.status(j1).state == "done"     # admitted once j0 released
    assert svc.service_metrics()["peak_admitted_reservation_bytes"] <= budget


def test_evict_pinned_handle_raises():
    """ISSUE 4 satellite: eviction of a handle whose chunks live plans
    still reference raises instead of corrupting the running jobs."""
    svc = DecompositionService(device_budget_bytes=64 << 20, queues=2)
    j0 = svc.submit(SubmitDecomposition(tensor=_t1(), rank=4, iters=2,
                                        seed=0, tol=0.0, build=BUILD))
    j1 = svc.submit(SubmitDecomposition(tensor=_t1(), rank=4, iters=2,
                                        seed=1, tol=0.0, build=BUILD))
    key = svc.scheduler.jobs[j0].handle.key
    assert svc.scheduler.jobs[j0].handle.pins == 2    # both live plans
    with pytest.raises(RuntimeError, match="pinned by 2 live plan"):
        svc.registry.evict(key)
    assert svc.registry.get(key) is not None          # still cached intact
    svc.run()
    assert svc.scheduler.jobs[j0].handle.pins == 0    # plans closed
    assert svc.registry.evict(key)                    # now safe
    assert svc.registry.get(key) is None


def test_oversized_job_rejected_at_submit():
    svc = DecompositionService(device_budget_bytes=1024, queues=4)
    with pytest.raises(ValueError, match="can never be admitted"):
        svc.submit(SubmitDecomposition(tensor=_t1(), rank=4, build=BUILD))
    # regression: a tiny reservation does NOT sneak a huge factor working
    # set past admission — rank-R factor bytes count in every regime
    t = _t1()
    probe = TensorRegistry()
    h = probe.register(t, build=BUILD)
    budget = h.spec.bytes_in_flight(4) + h.in_memory_bytes
    assert factor_bytes(t.dims, 4096, np.float32) > budget
    svc = DecompositionService(device_budget_bytes=budget, queues=4)
    with pytest.raises(ValueError, match="can never be admitted"):
        svc.submit(SubmitDecomposition(tensor=t, rank=4096, build=BUILD))


def test_unknown_job_id_raises_value_error():
    svc = DecompositionService(device_budget_bytes=64 << 20, queues=2)
    with pytest.raises(ValueError, match="no jobs submitted yet"):
        svc.status(0)
    j = svc.submit(SubmitDecomposition(tensor=_t1(), rank=4, iters=1,
                                       build=BUILD))
    svc.run()
    assert svc.status(j).state == "done"
    with pytest.raises(ValueError, match=r"unknown job id 7; known ids: 0..0"):
        svc.status(7)
    with pytest.raises(ValueError, match="unknown job id"):
        svc.result(j + 1)


def test_registry_fingerprint_semantics():
    reg = TensorRegistry()
    h1 = reg.register(_t1(), build=BUILD)
    h2 = reg.register(_t1(), build=BUILD)         # same content -> hit
    assert h1 is h2 and reg.hits == 1 and reg.misses == 1
    h3 = reg.register(_t1(), build=BuildParams(max_nnz_per_block=512))
    assert h3 is not h1 and reg.misses == 2       # build params change -> miss
    t_other = _t1(seed=7)
    h4 = reg.register(t_other, build=BUILD)       # different content -> miss
    assert h4 is not h1 and reg.misses == 3
    assert len(reg) == 3 and reg.host_bytes() > 0
    assert reg.evict(h3.key) and len(reg) == 2


def test_mttkrp_query_matches_in_memory():
    svc = DecompositionService(device_budget_bytes=64 << 20, queues=3)
    t = _t1()
    rng = np.random.default_rng(0)
    factors = [rng.standard_normal((d, 8)).astype(np.float32) for d in t.dims]
    b = core.build_blco(t, max_nnz_per_block=256)
    for mode in range(t.order):
        got = svc.mttkrp(MTTKRPQuery(tensor=t, factors=factors, mode=mode,
                                     build=BUILD))
        ref = core.mttkrp(b, factors, mode)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
    # all three queries + any later job reuse one cached BLCO build
    assert svc.registry.misses == 1 and svc.registry.hits == 2
    # query plans are closed: nothing left admitted or pooled
    assert svc.service_metrics()["admitted_reservation_bytes"] == 0
    assert svc.engine.resident_count == 0 and svc.engine.pool_size == 0


def test_failed_job_isolated_and_plan_released():
    svc = DecompositionService(device_budget_bytes=64 << 20, queues=2)
    good = svc.submit(SubmitDecomposition(tensor=_t1(), rank=4, iters=3,
                                          seed=0, build=BUILD))
    bad = svc.submit(SubmitDecomposition(tensor=_t2(), rank=4, iters=3,
                                         seed=0, build=BUILD))
    svc.scheduler.jobs[bad].mttkrp_fn = \
        lambda f, m: (_ for _ in ()).throw(RuntimeError("boom"))
    svc.run()
    assert svc.status(bad).state == "failed"
    assert "boom" in svc.status(bad).error
    assert svc.status(good).state == "done"       # unaffected tenant
    m = svc.service_metrics()
    assert m["admitted_reservation_bytes"] == 0   # plans closed on retire
    assert m["jobs_failed"] == 1 and m["jobs_completed"] == 1
    assert svc.engine.resident_count == 0 and svc.engine.pool_size == 0


def test_mttkrp_query_obeys_budget():
    """One-shot queries charge the same admission budget as jobs."""
    t = _t1()
    factors = [np.zeros((d, 4), np.float32) for d in t.dims]
    svc = DecompositionService(device_budget_bytes=1024, queues=4)
    with pytest.raises(ValueError, match="does not fit the device budget"):
        svc.mttkrp(MTTKRPQuery(tensor=t, factors=factors, mode=0, build=BUILD))
    assert svc.engine.pool_size == 0              # nothing leaked
    assert svc.engine.resident_count == 0
    assert svc.service_metrics()["admitted_reservation_bytes"] == 0
    with pytest.raises(ValueError, match="out of range"):
        DecompositionService().mttkrp(
            MTTKRPQuery(tensor=t, factors=factors, mode=7, build=BUILD))


def test_resumable_stepper_matches_one_shot():
    """cp_als == a loop of cp_als_step over CPState (the scheduler contract)."""
    t = _t1()
    b = core.build_blco(t)
    fn = lambda f, m: core.mttkrp(b, f, m)        # noqa: E731
    ref = core.cp_als(fn, t.dims, 5, norm_x=_norm(t), iters=6, seed=2)
    state = core.cp_als_init(t.dims, 5, norm_x=_norm(t), seed=2)
    for _ in range(6):
        core.cp_als_step(fn, state)
        if state.converged:
            break
    assert state.fits == ref.fits
    for a, b_ in zip(state.factors, ref.factors):
        assert np.array_equal(np.asarray(a), np.asarray(b_))
