"""Unified engine API: regime auto-selection, plan semantics, oracle parity.

Acceptance scenario: a tensor whose device footprint fits the budget yields
an InMemoryPlan, an oversized one yields a StreamedPlan, and both produce
MTTKRP results matching the dense oracle to fp32 tolerance across all
modes — one ``plan_for`` call, one ``ExecutionPlan`` surface.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro import core
from repro.core.launches import launch_cache_bytes
from repro.engine import (BASELINE_KINDS, BaselinePlan, DefaultEngine,
                          ExecutionPlan, InMemoryPlan, StreamedPlan,
                          factor_bytes, in_memory_bytes, plan_for)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tensor():
    return core.random_tensor((30, 22, 14), 1500, seed=6, dist="powerlaw")


def _factors(dims, rank, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((d, rank)).astype(np.float32) for d in dims]


def _rel_err(a, oracle):
    return np.max(np.abs(np.asarray(a, np.float64) - oracle)) / \
        (np.max(np.abs(oracle)) + 1e-30)


def test_plan_for_auto_selects_regime_and_matches_oracle():
    t = _tensor()
    b = core.build_blco(t, max_nnz_per_block=256)
    factors = _factors(t.dims, 8)
    fits = in_memory_bytes(b, 8) + factor_bytes(t.dims, 8, np.float32)

    big = plan_for(b, fits, rank=8)                   # exactly fits
    small = plan_for(b, fits - 1, rank=8, queues=2)   # one byte short
    assert isinstance(big, InMemoryPlan) and big.backend == "in_memory"
    assert isinstance(small, StreamedPlan) and small.backend == "streamed"
    assert isinstance(big, ExecutionPlan) and isinstance(small, ExecutionPlan)

    for mode in range(t.order):
        oracle = core.mttkrp_dense_oracle(t, factors, mode)
        for plan in (big, small):
            assert _rel_err(plan.mttkrp(factors, mode), oracle) < 5e-4, \
                (plan.backend, mode)
    big.close()
    small.close()


def test_plan_device_bytes_and_close():
    t = _tensor()
    b = core.build_blco(t, max_nnz_per_block=256)
    plan = plan_for(b, 1 << 30, rank=8)
    # exact resident footprint: hi + lo + vals + bases, 256-lane padded
    padded = -(-b.nnz // 256) * 256
    assert plan.device_bytes() == padded * (4 + 4 + 4 + 4 * b.order)
    assert plan.device_bytes() == launch_cache_bytes(b)
    # the regime's need adds one scan step's temporaries to the resident
    # bytes; they are transient, so the plan never holds them
    assert in_memory_bytes(b, 8) > plan.device_bytes()
    freed = plan.close()
    assert freed == launch_cache_bytes(b) and plan.device_bytes() == 0
    assert plan.close() == 0                          # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        plan.mttkrp(_factors(t.dims, 8), 0)

    stream = plan_for(b, 1 << 30, rank=8, backend="streamed", queues=3)
    assert stream.device_bytes() == stream.spec.bytes_in_flight(3)
    assert stream.close() == stream.spec.bytes_in_flight(3)
    assert stream.device_bytes() == 0


def test_no_regime_fits_raises():
    b = core.build_blco(_tensor(), max_nnz_per_block=256)
    with pytest.raises(ValueError, match="no regime fits"):
        plan_for(b, 1024, rank=8)
    with pytest.raises(ValueError, match="unknown backend"):
        plan_for(b, 1 << 30, rank=8, backend="nope")
    # explicit backends enforce the budget too (no silent bypass)
    with pytest.raises(ValueError, match="in-memory plan needs"):
        plan_for(b, 1024, rank=8, backend="in_memory")


def test_engine_stats_timing_split():
    t = _tensor()
    b = core.build_blco(t, max_nnz_per_block=128)
    plan = plan_for(b, 1 << 30, rank=4, backend="streamed", queues=2)
    plan.mttkrp(_factors(t.dims, 4), 0)
    s = plan.stats()
    assert s.backend == "streamed" and s.mttkrp_calls == 1
    assert s.launches == len(b.launches) and s.h2d_bytes > 0
    # the fenced device span covers (at least) the async dispatch span, and
    # the deprecated alias reads the fenced number
    assert s.device_time_s >= s.dispatch_time_s > 0
    assert s.compute_time_s == s.device_time_s
    assert s.total_time_s >= s.device_time_s
    plan.close()


@pytest.mark.parametrize("kind", BASELINE_KINDS)
def test_baseline_plans_from_blco_decode(kind):
    """BLCO's single copy decodes back to full coordinates: baseline plans
    built straight from the BLCO encoding match the oracle."""
    t = _tensor()
    b = core.build_blco(t, max_nnz_per_block=256)
    factors = _factors(t.dims, 8)
    plan = plan_for(b, 1 << 30, rank=8, backend=kind)
    assert isinstance(plan, BaselinePlan) and plan.backend == kind
    for mode in range(t.order):
        oracle = core.mttkrp_dense_oracle(t, factors, mode)
        assert _rel_err(plan.mttkrp(factors, mode), oracle) < 5e-4, mode
    assert plan.device_bytes() > 0
    plan.close()


def test_decode_coords_roundtrip():
    t = _tensor()
    b = core.build_blco(t, target_bits=12, max_nnz_per_block=64)
    coords = core.decode_coords(b)
    # same multiset of (coords, value) rows as the original tensor
    got = {tuple(c) + (float(v),) for c, v in zip(coords, b.values)}
    want = {tuple(c) + (float(v),) for c, v in zip(t.indices, t.values)}
    assert got == want


def test_cp_als_accepts_plan_engine_and_callable():
    t = _tensor()
    b = core.build_blco(t)
    norm = float(np.linalg.norm(t.values))
    plan = plan_for(b, 1 << 30, rank=5)
    r_plan = core.cp_als(plan, t.dims, 5, norm_x=norm, iters=4, seed=2)
    r_fn = core.cp_als(lambda f, m: plan.mttkrp(f, m), t.dims, 5,
                       norm_x=norm, iters=4, seed=2)
    assert r_plan.fits == r_fn.fits
    for a, b_ in zip(r_plan.factors, r_fn.factors):
        assert np.array_equal(np.asarray(a), np.asarray(b_))
    with pytest.raises(TypeError, match="MTTKRP backend"):
        core.as_mttkrp_fn(42)
    plan.close()


def test_default_engine_protocol():
    b = core.build_blco(_tensor(), max_nnz_per_block=256)
    eng = DefaultEngine(queues=2)
    plan = eng.plan(b, device_budget_bytes=1 << 30, rank=6)
    assert plan.backend == "in_memory"
    plan.close()
    fits = in_memory_bytes(b, 6) + factor_bytes(b.dims, 6, np.float32)
    plan = eng.plan(b, device_budget_bytes=fits - 1, rank=6)
    assert plan.backend == "streamed"
    plan.close()


def test_zero_nnz_plans():
    t = core.from_coo(np.zeros((0, 3), np.int64), np.zeros((0,), np.float32),
                      (8, 6, 4))
    b = core.build_blco(t)
    factors = _factors(t.dims, 5)
    for backend in ("in_memory", "streamed"):
        plan = plan_for(b, 1 << 30, rank=5, backend=backend)
        out = np.asarray(plan.mttkrp(factors, 0))
        assert out.shape == (8, 5)
        np.testing.assert_array_equal(out, 0.0)
        plan.close()


def test_sharded_plan_via_mesh_context():
    """plan_for routes to ShardedPlan when a mesh is active (subprocess:
    fake XLA device count must be set before jax initializes)."""
    code = """
        import numpy as np
        from repro import core
        from repro.dist.context import set_mesh
        from repro.engine import plan_for
        from repro.launch.mesh import make_test_mesh
        set_mesh(make_test_mesh((4, 2), ("data", "model")))
        t = core.random_tensor((64, 33, 17), 4000, seed=5, dist="powerlaw")
        b = core.build_blco(t, target_bits=10, max_nnz_per_block=512)
        plan = plan_for(b, 1 << 30, rank=8)
        assert plan.backend == "sharded", plan.backend
        # nnz arrays shard over data (4) and replicate over model (2):
        # footprint counts every model-axis replica; each shard pads to a
        # size class so the scan step cuts it evenly
        from repro.core.padding import pad_bucket
        per = pad_bucket(-(-b.nnz // 4))
        assert plan.device_bytes() == per * 4 * (4 + 4 + 4 + 4 * 3) * 2
        from repro.engine import sharded_bytes
        assert plan.device_bytes() == sharded_bytes(b, plan.mesh)
        # every device holds one data shard (its model-axis replica)
        per_dev = plan.bytes_by_device()
        assert len(per_dev) == 8
        assert set(per_dev.values()) == {per * (4 + 4 + 4 + 4 * 3)}
        assert sum(per_dev.values()) == plan.device_bytes()
        # an undersized budget is rejected before any device upload
        try:
            plan_for(b, plan.device_bytes() // 2, rank=8)
            raise SystemExit("expected ValueError")
        except ValueError as e:
            assert "sharded plan needs" in str(e)
        rng = np.random.default_rng(0)
        factors = [rng.standard_normal((d, 8)).astype(np.float32)
                   for d in t.dims]
        for mode in range(t.order):
            oracle = core.mttkrp_dense_oracle(t, factors, mode)
            out = np.asarray(plan.mttkrp(factors, mode), np.float64)
            rel = np.max(np.abs(out - oracle)) / np.max(np.abs(oracle))
            assert rel < 5e-4, (mode, rel)
        assert plan.close() > 0
        print("SHARDED_OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8")
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    assert "SHARDED_OK" in p.stdout
