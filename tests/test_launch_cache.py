"""Device-resident launch cache: single-dispatch execution, exactness,
byte accounting, and the engine's fenced in-memory timing.

Acceptance scenario (ISSUE 3): an in-memory BLCO MTTKRP issues exactly ONE
jitted dispatch per call — assertable via the dispatch counter — with zero
per-call host-side numpy padding, and matches both the dense oracle and the
legacy per-launch loop to f32 rounding.
"""
import numpy as np
import pytest

from repro import core
from repro.core.launches import (LaunchCache, default_reservation,
                                 launch_cache_bytes, scan_temp_bytes)
from repro.core.padding import (LANE, PAPER_MAX_NNZ_PER_BLOCK,
                                default_launch_nnz, next_pow2, pad_bucket,
                                pad_multiple, pad_pow2, scan_step)
from repro.engine import factor_bytes, in_memory_bytes, plan_for


def _tensor():
    return core.random_tensor((40, 25, 30), 2000, seed=1, dist="powerlaw")


def _factors(dims, rank=8, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((d, rank)).astype(np.float32) for d in dims]


def _rel_err(a, oracle):
    return np.max(np.abs(np.asarray(a, np.float64) - oracle)) / \
        (np.max(np.abs(oracle)) + 1e-30)


def test_padding_helpers_shared():
    """One home for the pow2/lane arithmetic (was three private copies)."""
    assert next_pow2(1) == 1 and next_pow2(3) == 4 and next_pow2(256) == 256
    assert pad_pow2(5) == LANE and pad_pow2(300) == 512
    assert pad_multiple(1) == LANE and pad_multiple(257) == 512
    assert pad_multiple(512) == 512
    # size-class buckets: LANE multiples, >= n, <= 25% waste above 1024,
    # and boundedly many distinct values (the cache-churn invariant)
    assert pad_bucket(1) == LANE and pad_bucket(256) == 256
    assert pad_bucket(2048) == 2048 and pad_bucket(2049) == 2560
    for n in (1, 255, 257, 1023, 5000, 1 << 20, (1 << 20) + 1):
        b = pad_bucket(n)
        assert b >= n and b % LANE == 0
        if n > 1024:
            assert b - n <= n // 4
    assert len({pad_bucket(n) for n in range(1, 1 << 16)}) <= \
        8 * 16 + 2                        # <= 8 classes per octave
    # monotone: a bigger launch never gets a smaller reservation
    vals = [pad_bucket(n) for n in range(1, 1 << 13)]
    assert vals == sorted(vals)


def test_single_dispatch_per_call_vs_per_launch_loop():
    t = _tensor()
    b = core.build_blco(t, target_bits=12, max_nnz_per_block=256)
    assert len(b.launches) > 1            # the regime that matters
    factors = _factors(t.dims)

    c0 = core.dispatch_count()
    core.mttkrp_per_launch(b, factors, 0)
    assert core.dispatch_count() - c0 == len(b.launches)

    c0 = core.dispatch_count()
    out = core.mttkrp(b, factors, 0)
    assert core.dispatch_count() - c0 == 1          # ONE dispatch, L launches

    # the cache is built once and reattached calls stay single-dispatch
    cache = b._launch_cache
    c0 = core.dispatch_count()
    out2 = core.mttkrp(b, factors, 1)
    assert core.dispatch_count() - c0 == 1
    assert b._launch_cache is cache                  # no rebuild
    assert out.shape == (t.dims[0], 8) and out2.shape == (t.dims[1], 8)


def test_cache_matches_loop_bitwise_and_oracle():
    t = _tensor()
    b = core.build_blco(t, target_bits=12, max_nnz_per_block=256)
    factors = _factors(t.dims)
    for mode in range(t.order):
        oracle = core.mttkrp_dense_oracle(t, factors, mode)
        for res in ("register", "hierarchical", "direct"):
            cached = core.mttkrp(b, factors, mode, resolution=res)
            loop = core.mttkrp_per_launch(b, factors, mode, resolution=res)
            # same launch order and padding, but the scan body and the
            # per-launch program are compiled apart, so XLA may associate
            # their sums differently: equal to f32 rounding (1e-6 of the
            # largest output, about 8 ulp)
            loop = np.asarray(loop)
            np.testing.assert_allclose(
                np.asarray(cached), loop, rtol=0,
                atol=1e-6 * np.max(np.abs(loop)), err_msg=res)
            assert _rel_err(cached, oracle) < 5e-4, (mode, res)


def test_in_memory_plan_single_dispatch_both_kernels():
    t = _tensor()
    b = core.build_blco(t, target_bits=12, max_nnz_per_block=256)
    factors = _factors(t.dims)
    for kernel in ("xla", "pallas"):
        plan = plan_for(b, 1 << 40, rank=8, backend="in_memory",
                        kernel=kernel)
        for mode in range(t.order):
            c0 = core.dispatch_count()
            out = plan.mttkrp(factors, mode)
            assert core.dispatch_count() - c0 == 1, (kernel, mode)
            oracle = core.mttkrp_dense_oracle(t, factors, mode)
            assert _rel_err(out, oracle) < 5e-4, (kernel, mode)
        plan.close()


def test_in_memory_plan_records_fenced_timing():
    """Satellite: InMemoryPlan fills dispatch/device/launches EngineStats so
    in-memory vs streamed comparisons are apples-to-apples."""
    t = _tensor()
    b = core.build_blco(t, max_nnz_per_block=256)
    plan = plan_for(b, 1 << 40, rank=4, backend="in_memory")
    plan.mttkrp(_factors(t.dims, 4), 0)
    plan.mttkrp(_factors(t.dims, 4), 1)
    s = plan.stats()
    assert s.backend == "in_memory" and s.mttkrp_calls == 2
    assert s.launches == 2                 # one fused dispatch per call
    assert s.device_time_s >= s.dispatch_time_s > 0
    assert s.total_time_s >= s.device_time_s
    assert s.h2d_bytes == plan.device_bytes()        # the one upload
    plan.close()


def test_cache_bytes_accounting():
    t = _tensor()
    b = core.build_blco(t, target_bits=12, max_nnz_per_block=256)
    cache = LaunchCache.from_blco(b)
    max_launch = max(l.nnz for l in b.launches)
    res = default_reservation(max_launch)
    assert cache.reservation == res
    assert cache.num_launches == len(b.launches)
    per_elem = 4 + 4 + b.values.dtype.itemsize + 4 * b.order
    want = len(b.launches) * res * per_elem
    assert cache.device_bytes() == want
    assert launch_cache_bytes(b) == want
    # engine admission sees the same, plus one scan step's temporaries
    assert in_memory_bytes(b, 8) == want + scan_temp_bytes(res, b.order, 8, 4)
    plan = plan_for(b, 1 << 40, rank=8, backend="in_memory")
    assert plan.device_bytes() == want
    assert plan.close() == want
    cache.delete()
    assert cache.device_bytes() == 0
    with pytest.raises(RuntimeError, match="closed"):
        cache.mttkrp(_factors(t.dims), 0)


def test_cache_reservation_validation_and_flat_stream():
    t = _tensor()
    b = core.build_blco(t, target_bits=12, max_nnz_per_block=256)
    max_launch = max(l.nnz for l in b.launches)
    with pytest.raises(ValueError, match="smaller than largest"):
        LaunchCache.from_blco(b, reservation_nnz=max_launch - 1)
    cache = LaunchCache.from_blco(b, reservation_nnz=pad_pow2(max_launch))
    hi, lo, vals, bases = cache.flat()
    assert hi.shape == (cache.num_launches * cache.reservation,)
    assert bases.shape == (hi.shape[0], b.order)
    cache.delete()


def test_ragged_explicit_reservation_rounds_to_lane():
    """ISSUE 4 satellite: an explicit non-LANE reservation_nnz is rounded
    up, so the actual footprint matches the launch_cache_bytes predictor
    and the fused Pallas tiler always sees a tile-divisible reservation."""
    t = _tensor()
    # a non-pow2 block budget gives launches whose max is NOT a LANE multiple
    b = core.build_blco(t, target_bits=12, max_nnz_per_block=200)
    max_launch = max(l.nnz for l in b.launches)
    ragged = max_launch + 3                    # deliberately not a multiple
    assert ragged % LANE != 0
    cache = LaunchCache.from_blco(b, reservation_nnz=ragged)
    assert cache.reservation == pad_multiple(ragged)
    assert cache.reservation % LANE == 0
    # the default reservation equals the predictor even for ragged nnz
    default = LaunchCache.from_blco(b)
    assert max_launch % LANE != 0              # the ragged regime is real
    assert default.device_bytes() == launch_cache_bytes(b)
    factors = _factors(t.dims)
    oracle = core.mttkrp_dense_oracle(t, factors, 0)
    assert _rel_err(cache.mttkrp(factors, 0), oracle) < 5e-4
    cache.delete()
    default.delete()


def test_dtype_parity_xla_pallas_per_launch():
    """ISSUE 4 satellite: float64 tensor values against float32 factors
    accumulate in float64 on EVERY path (jnp.result_type), instead of the
    stacked accumulator silently downcasting to the factor dtype."""
    import jax
    from repro.kernels.fused import fused_cache_mttkrp

    t = _tensor()
    with jax.enable_x64(True):
        t64 = core.from_coo(np.asarray(t.indices),
                            np.asarray(t.values, np.float64), t.dims)
        b = core.build_blco(t64, target_bits=12, max_nnz_per_block=256)
        cache = LaunchCache.from_blco(b)
        factors = _factors(t.dims)             # float32 on purpose
        assert cache.vals.dtype == np.float64
        oracle = core.mttkrp_dense_oracle(t64, factors, 0)
        stacked = cache.mttkrp(factors, 0)
        loop = core.mttkrp_per_launch(b, factors, 0)
        fused = fused_cache_mttkrp(cache, factors, 0)
        for name, out in (("stacked", stacked), ("per_launch", loop),
                          ("pallas", fused)):
            assert out.dtype == np.float64, name
            assert _rel_err(out, oracle) < 1e-10, name
        cache.delete()


def test_zero_nnz_cache():
    t = core.from_coo(np.zeros((0, 3), np.int64), np.zeros((0,), np.float32),
                      (8, 6, 4))
    b = core.build_blco(t)
    assert launch_cache_bytes(b) == 0
    c0 = core.dispatch_count()
    out = core.mttkrp(b, _factors(t.dims, 5), 0)
    assert core.dispatch_count() == c0     # nothing to dispatch
    assert out.shape == (8, 5)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_streamed_kernel_validation():
    b = core.build_blco(_tensor(), max_nnz_per_block=256)
    with pytest.raises(ValueError, match="unknown kernel"):
        plan_for(b, 1 << 40, rank=8, backend="in_memory", kernel="cuda")
    with pytest.raises(ValueError, match="unknown kernel"):
        core.DeviceBLCO(b, kernel="cuda")
    # kernel= is validated consistently on every backend, not silently
    # ignored where it cannot apply
    with pytest.raises(ValueError, match="not supported on baseline"):
        plan_for(b, 1 << 40, rank=8, backend="coo", kernel="pallas")
    plan = plan_for(b, 1 << 40, rank=8, backend="streamed", kernel="pallas",
                    queues=2)
    factors = _factors(b.dims)
    t = _tensor()
    oracle = core.mttkrp_dense_oracle(t, factors, 0)
    c0 = core.dispatch_count()
    out = plan.mttkrp(factors, 0)
    # exactly one dispatch per streamed chunk (no double count on pallas)
    assert core.dispatch_count() - c0 == len(b.launches)
    assert _rel_err(out, oracle) < 5e-4
    plan.close()


def test_clear_launch_cache_releases_attached_copy():
    t = _tensor()
    b = core.build_blco(t, max_nnz_per_block=256)
    assert core.clear_launch_cache(b) == 0            # nothing attached yet
    factors = _factors(t.dims)
    core.mttkrp(b, factors, 0)
    cache = b._launch_cache
    held = cache.device_bytes()
    assert held > 0
    assert core.clear_launch_cache(b) == held
    assert cache.closed and b._launch_cache is None
    # a later call transparently rebuilds the cache
    oracle = core.mttkrp_dense_oracle(t, factors, 0)
    assert _rel_err(core.mttkrp(b, factors, 0), oracle) < 5e-4


def test_launch_and_step_sizes_follow_device_memory():
    """Launch sizes and scan steps come from the device's memory: the
    paper's 2^27 nnz per launch and whole-launch steps where the backend
    reports no limit, 2^21 nnz launches at order 3 on a 16 GB chip, and
    steps that halve as the rank grows so their temporaries stay within
    an eighth of the chip."""
    v5e = 16_909_336_064
    assert default_launch_nnz(3, 4, None) == PAPER_MAX_NNZ_PER_BLOCK
    assert default_launch_nnz(3, 4, v5e) == 1 << 21
    assert default_launch_nnz(5, 4, v5e) == 1 << 20    # wider slots
    assert scan_step(1 << 21, order=3, rank=32, itemsize=4,
                     device_bytes=None) == 1 << 21
    assert scan_step(1 << 21, order=3, rank=32, itemsize=4,
                     device_bytes=v5e) == 1 << 21
    assert scan_step(1 << 21, order=3, rank=64, itemsize=4,
                     device_bytes=v5e) == 1 << 20
    assert scan_step(768, order=3, rank=8, itemsize=4, device_bytes=1) == 3


@pytest.mark.parametrize("backend", ["in_memory", "streamed"])
def test_scan_steps_cut_launches_and_match_oracle(monkeypatch, backend):
    """Where a launch's temporaries would crowd the device, the scan runs
    it in steps (here 256 of its 1024 slots); the result is unchanged."""
    import repro.core.launches as launches
    # 2 MB device: a 1024-slot step at rank 8 (616 B/slot x 8) does not fit
    monkeypatch.setattr(launches, "device_memory_bytes", lambda: 2_000_000)
    assert launches.scan_step_for(1024, 3, 8, 4) == 256
    t = _tensor()
    b = core.build_blco(t, target_bits=12, max_nnz_per_block=1024)
    assert max(l.nnz for l in b.launches) > 512
    plan = plan_for(b, 1 << 40, rank=8, backend=backend, queues=2)
    factors = _factors(t.dims)
    for mode in range(t.order):
        oracle = core.mttkrp_dense_oracle(t, factors, mode)
        assert _rel_err(plan.mttkrp(factors, mode), oracle) < 5e-4, mode
    plan.close()


def test_scan_module_name_is_stable():
    """The in-memory scan compiles to ``jit_stacked_mttkrp``: the trace
    readers find the MTTKRP's device time under that name."""
    import jax.numpy as jnp
    from repro.core.launches import stacked_mttkrp
    b = core.build_blco(_tensor(), max_nnz_per_block=256)
    cache = LaunchCache.from_blco(b)
    factors = tuple(jnp.asarray(f) for f in _factors(b.dims))
    text = stacked_mttkrp.lower(
        cache.hi, cache.lo, cache.vals, cache.bases, factors,
        re_fields=cache.re_fields, re_shifts=cache.re_shifts, mode=0,
        out_rows=b.dims[0], resolution="register", copies=1,
        step=cache.reservation).as_text()
    assert "module @jit_stacked_mttkrp" in text
