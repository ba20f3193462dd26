"""The MXU reduction (``resolution="mxu"``): a one-hot matmul of the three
bfloat16 terms of each float32 Hadamard row sums the rows into the output.

It must agree with the dense oracle to float32 rounding (a single bfloat16
pass would not), on every mode and every path that scans launches, and
``"auto"`` must pick it only on a TPU, for a float32 output of at most
``MXU_MAX_ROWS`` rows.  The TPU is steered in the tests by patching
``jax.default_backend``; the arithmetic runs on XLA:CPU.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import core
from repro.core import counters
from repro.core.launches import LaunchCache, stacked_mttkrp
from repro.core.mttkrp import (MXU_MAX_ROWS, _bf16_head, _split_bf16,
                               auto_resolution)
from repro.engine import plan_for
from repro.obs import trace as obs_trace

# (dims, nnz, dist, target_bits, max_nnz): orders 3 and 4, several launches,
# mode lengths that are not multiples of 128
CASES = [
    ((13, 7, 29, 5), 500, "powerlaw", 8, 64),
    ((40, 25, 30), 2000, "powerlaw", 12, 512),
    ((300, 9, 130), 3000, "uniform", 12, 700),
]


def _factors(dims, rank=8, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((d, rank)).astype(np.float32) for d in dims]


def _rel_err(a, oracle):
    return np.max(np.abs(np.asarray(a, np.float64) - oracle)) / \
        (np.max(np.abs(oracle)) + 1e-30)


@pytest.fixture
def on_tpu(monkeypatch):
    """``auto_resolution`` sees a TPU backend."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_split_bf16_is_exact():
    """The three bfloat16 terms of a float32 sum back to it exactly, over
    magnitudes from 1e-20 to 1e20.  The terms are cut by bit masks, so the
    final conversion to bfloat16 rounds nothing: a backend that keeps a
    conversion at float32 (XLA's excess precision on a TPU) gets the same
    terms."""
    rng = np.random.default_rng(3)
    p = (rng.standard_normal((4096, 8))
         * 10.0 ** rng.integers(-20, 20, (4096, 8))).astype(np.float32)
    head = _bf16_head(jnp.asarray(p))
    np.testing.assert_array_equal(
        np.asarray(head.astype(jnp.bfloat16).astype(jnp.float32)), head)
    assert np.all(np.abs(p - np.asarray(head)) < 2.0 ** -7 * np.abs(p))
    terms = np.asarray(_split_bf16(jnp.asarray(p)), np.float32)
    assert terms.shape == (4096, 24)
    back = terms[:, :8] + terms[:, 8:16] + terms[:, 16:]
    np.testing.assert_array_equal(back, p)


@pytest.mark.parametrize("dims,nnz,dist,tb,mx", CASES)
def test_mxu_matches_oracle_and_register(dims, nnz, dist, tb, mx):
    """Every mode, multi-launch caches with padding slots: float32 accuracy
    (1e-5 of the largest entry; one bfloat16 pass reads about 1e-3)."""
    t = core.random_tensor(dims, nnz, seed=1, dist=dist)
    b = core.build_blco(t, target_bits=tb, max_nnz_per_block=mx)
    cache = LaunchCache.from_blco(b)
    assert cache.num_launches > 1
    assert cache.num_launches * cache.reservation > t.nnz   # padding slots
    factors = _factors(dims)
    for mode in range(len(dims)):
        oracle = core.mttkrp_dense_oracle(t, factors, mode)
        mxu = np.asarray(cache.mttkrp(factors, mode, resolution="mxu"))
        reg = np.asarray(cache.mttkrp(factors, mode, resolution="register"))
        assert mxu.shape == (dims[mode], 8)
        assert _rel_err(mxu, oracle) < 1e-5, mode
        np.testing.assert_allclose(mxu, reg, rtol=0,
                                   atol=1e-5 * np.max(np.abs(reg)))
    cache.delete()


@pytest.mark.parametrize("backend", ["in_memory", "streamed"])
def test_mxu_scan_steps_shorter_than_reservation(monkeypatch, on_tpu,
                                                 backend):
    """Steps of 256 of a launch's 1024 slots, through the plans that run
    the scan, with ``"auto"`` resolving to the MXU."""
    import repro.core.launches as launches
    monkeypatch.setattr(launches, "device_memory_bytes", lambda: 2_000_000)
    assert launches.scan_step_for(1024, 3, 8, 4) == 256
    t = core.random_tensor((40, 25, 30), 2000, seed=1, dist="powerlaw")
    b = core.build_blco(t, target_bits=12, max_nnz_per_block=1024)
    plan = plan_for(b, 1 << 40, rank=8, backend=backend, queues=2)
    factors = _factors(t.dims)
    before = counters.resolution_counts()
    for mode in range(t.order):
        oracle = core.mttkrp_dense_oracle(t, factors, mode)
        assert _rel_err(plan.mttkrp(factors, mode), oracle) < 1e-5, mode
    after = counters.resolution_counts()
    assert after.get("mxu", 0) - before.get("mxu", 0) == t.order
    plan.close()


def test_mxu_sharded_matches_oracle(on_tpu):
    """The sharded regime's scan, on a one-device data x model mesh."""
    from repro.core.distributed import make_distributed_mttkrp
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    t = core.random_tensor((64, 33, 17), 3000, seed=5, dist="powerlaw")
    b = core.build_blco(t, target_bits=10, max_nnz_per_block=512)
    factors = _factors(t.dims)
    run = make_distributed_mttkrp(b, mesh)
    before = counters.resolution_counts().get("mxu", 0)
    for mode in range(t.order):
        oracle = core.mttkrp_dense_oracle(t, factors, mode)
        assert _rel_err(run(factors, mode), oracle) < 1e-5, mode
    assert counters.resolution_counts()["mxu"] - before == t.order


def test_mxu_refuses_a_non_float32_output():
    """Three bfloat16 terms hold a float32, not a float64 or a bfloat16."""
    t = core.random_tensor((40, 25, 30), 500, seed=1)
    cache = LaunchCache.from_blco(core.build_blco(t))
    factors = tuple(jnp.asarray(f, jnp.bfloat16) for f in _factors(t.dims))
    with pytest.raises(ValueError, match="mxu"):
        stacked_mttkrp(cache.hi, cache.lo, cache.vals.astype(jnp.bfloat16),
                       cache.bases, factors, re_fields=cache.re_fields,
                       re_shifts=cache.re_shifts, mode=0, out_rows=40,
                       resolution="mxu", copies=1, step=cache.reservation)


@pytest.mark.parametrize("rows,dtype,expected", [
    (6186, jnp.float32, "mxu"),
    (24, jnp.float32, "mxu"),
    (MXU_MAX_ROWS, jnp.float32, "mxu"),
    (MXU_MAX_ROWS + 1, jnp.float32, "register"),
    (28818, np.float64, "register"),
    (24, np.float64, "hierarchical"),
    (6186, jnp.bfloat16, "register"),
])
def test_auto_resolution_on_tpu(on_tpu, rows, dtype, expected):
    assert auto_resolution(rows, dtype) == expected
    if expected != "mxu":
        assert expected == core.choose_resolution(rows)


@pytest.mark.parametrize("rows", [24, 127, 128, 6186, 28818, 1 << 20])
def test_auto_resolution_on_cpu_is_the_paper_rule(rows):
    """On XLA:CPU ``"auto"`` resolves exactly as before the MXU path."""
    assert jax.default_backend() == "cpu"
    assert auto_resolution(rows, jnp.float32) == core.choose_resolution(rows)


@pytest.mark.parametrize("tpu", [False, True])
def test_resolution_counts_one_per_mttkrp(monkeypatch, tpu):
    """A CP-ALS sweep of an in-memory plan counts one resolution per
    MTTKRP call, and its ``launch_cache.scan`` spans name it."""
    if tpu:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    t = core.random_tensor((13, 7, 290, 5), 800, seed=2, dist="powerlaw")
    b = core.build_blco(t, target_bits=8, max_nnz_per_block=128)
    plan = plan_for(b, 1 << 40, rank=4, backend="in_memory")
    state = core.cp_als_init(t.dims, 4, norm_x=1.0, seed=0)
    expected = ["mxu"] * 4 if tpu else \
        [core.choose_resolution(d) for d in t.dims]
    before = counters.resolution_counts()
    with obs_trace.enabled():
        obs_trace.drain()
        for _ in range(2):
            core.cp_als_step(plan, state)
        spans = [s for s in obs_trace.drain()
                 if s.name == "launch_cache.scan"]
    after = counters.resolution_counts()
    delta = {k: after[k] - before.get(k, 0) for k in after
             if after[k] != before.get(k, 0)}
    want = {}
    for r in expected * 2:
        want[r] = want.get(r, 0) + 1
    assert delta == want
    assert [s.attrs["resolution"] for s in spans] == expected * 2
    plan.close()
