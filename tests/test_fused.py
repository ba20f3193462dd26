"""Fused single-pallas_call MTTKRP vs the dense matricization oracle.

Satellite coverage (ISSUE 3): orders 3-5, both conflict resolutions
(``register`` -> segment variant, ``hierarchical`` -> stash variant on the
short-mode tensors), ragged nnz counts that exercise the reservation
padding slots.  Off the TPU the kernel runs in the Pallas interpreter;
on a TPU it is refused (Mosaic does not compile its row gather, see
``tests/test_tpu_compile.py``).
"""
import numpy as np
import pytest

from repro import core
from repro.core.launches import LaunchCache
from repro.kernels import (fused_cache_mttkrp, pallas_mttkrp,
                           pallas_mttkrp_phases)
from repro.kernels.fused import STASH_MAX_ROWS, _variant_for

# (dims, nnz, target_bits, max_nnz_per_block) — ragged nnz on purpose: none
# is a multiple of the 256-slot tile, so every launch ends in padding slots
CASES = [
    ((70, 40, 30), 1777, 12, 512),             # order 3
    ((13, 7, 29, 5), 499, 8, 64),              # order 4, forced blocking
    ((128, 4, 256, 8, 3), 801, 16, 128),       # order 5
]


def _factors(dims, rank=8, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((d, rank)).astype(np.float32) for d in dims]


def _rel_err(a, oracle):
    return np.max(np.abs(np.asarray(a, np.float64) - oracle)) / \
        (np.max(np.abs(oracle)) + 1e-30)


@pytest.mark.parametrize("resolution", ["register", "hierarchical"])
@pytest.mark.parametrize("dims,nnz,tb,mx", CASES)
def test_fused_matches_oracle_all_modes(dims, nnz, tb, mx, resolution):
    t = core.random_tensor(dims, nnz, seed=7, dist="powerlaw")
    b = core.build_blco(t, target_bits=tb, max_nnz_per_block=mx)
    factors = _factors(dims)
    for mode in range(t.order):
        oracle = core.mttkrp_dense_oracle(t, factors, mode)
        out = pallas_mttkrp(b, factors, mode, resolution=resolution)
        assert _rel_err(out, oracle) < 5e-4, (mode, resolution)


def test_fused_exercises_both_variants():
    """The parametrized sweep hits the stash (hierarchical) variant on the
    short-mode cases and the segment variant on everything else; the mode
    -> variant mapping follows the §5.3 heuristic with the VMEM row bound."""
    assert _variant_for("hierarchical", STASH_MAX_ROWS) == "stash"
    assert _variant_for("hierarchical", STASH_MAX_ROWS + 1) == "segment"
    assert _variant_for("register", 4) == "segment"
    assert _variant_for("auto", 4) == "segment"        # resolved upstream
    # every CASES dims fits the stash bound, so the hierarchical sweep above
    # really ran the stash kernel on all modes
    assert all(d <= STASH_MAX_ROWS for dims, _, _, _ in CASES for d in dims)
    # and a long target mode falls back to the segment kernel + scatter
    t = core.random_tensor((600, 9, 8), 700, seed=11, dist="powerlaw")
    b = core.build_blco(t, target_bits=12, max_nnz_per_block=256)
    factors = _factors(t.dims)
    oracle = core.mttkrp_dense_oracle(t, factors, 0)
    out = pallas_mttkrp(b, factors, 0, resolution="hierarchical")
    assert _rel_err(out, oracle) < 5e-4


@pytest.mark.parametrize("dims,nnz,tb,mx", CASES[:2])
def test_fused_single_dispatch_and_no_host_padding(dims, nnz, tb, mx):
    t = core.random_tensor(dims, nnz, seed=3, dist="powerlaw")
    b = core.build_blco(t, target_bits=tb, max_nnz_per_block=mx)
    factors = _factors(dims)
    cache = LaunchCache.from_blco(b)
    # warm the jit cache, then count: exactly ONE dispatch per call
    fused_cache_mttkrp(cache, factors, 0)
    c0 = core.dispatch_count()
    fused_cache_mttkrp(cache, factors, 0)
    assert core.dispatch_count() - c0 == 1
    # the three-phase reference records its three device phases
    c0 = core.dispatch_count()
    pallas_mttkrp_phases(b, factors, 0, cache=cache)
    assert core.dispatch_count() - c0 == 3
    cache.delete()


def test_fused_agrees_with_three_phase_reference():
    t = core.random_tensor((40, 25, 30), 1500, seed=5, dist="powerlaw")
    b = core.build_blco(t, target_bits=12, max_nnz_per_block=512)
    factors = _factors(t.dims)
    for mode in range(t.order):
        fused = np.asarray(pallas_mttkrp(b, factors, mode), np.float64)
        phases = np.asarray(pallas_mttkrp_phases(b, factors, mode),
                            np.float64)
        np.testing.assert_allclose(fused, phases, rtol=1e-5, atol=1e-5)


def test_fused_padding_slots_are_exact():
    """Reservation padding contributes zero: growing the reservation (more
    pad slots per launch, different tile boundaries) leaves the result
    unchanged up to summation order."""
    t = core.random_tensor((30, 22, 14), 1003, seed=9, dist="powerlaw")
    b = core.build_blco(t, target_bits=12, max_nnz_per_block=256)
    factors = _factors(t.dims)
    tight = LaunchCache.from_blco(b)
    loose = LaunchCache.from_blco(b, reservation_nnz=2 * tight.reservation)
    for mode in range(t.order):
        oracle = core.mttkrp_dense_oracle(t, factors, mode)
        a = fused_cache_mttkrp(tight, factors, mode)
        c = fused_cache_mttkrp(loose, factors, mode)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-5, atol=1e-5)
        assert _rel_err(c, oracle) < 5e-4, mode
    tight.delete()
    loose.delete()


def test_pallas_kernel_refused_where_it_cannot_compile(monkeypatch):
    """On a TPU the fused kernel would have to compile, and Mosaic refuses
    it: every entry point raises up front instead of running the
    interpreter there or failing deep inside a plan."""
    import repro.kernels.fused as fused
    from repro.engine import plan_for
    t = core.random_tensor((70, 40, 30), 500, seed=7, dist="powerlaw")
    b = core.build_blco(t, target_bits=12, max_nnz_per_block=512)
    factors = _factors(t.dims)
    monkeypatch.setattr(fused, "interpret_mode", lambda: False)
    for backend in ("in_memory", "streamed"):
        with pytest.raises(NotImplementedError, match="does not compile"):
            plan_for(b, 1 << 40, rank=8, backend=backend, kernel="pallas")
    with pytest.raises(NotImplementedError, match="does not compile"):
        pallas_mttkrp(b, factors, 0)
    # the XLA kernel is unaffected
    plan = plan_for(b, 1 << 40, rank=8, backend="in_memory")
    oracle = core.mttkrp_dense_oracle(t, factors, 0)
    assert _rel_err(plan.mttkrp(factors, 0), oracle) < 5e-4
    plan.close()
