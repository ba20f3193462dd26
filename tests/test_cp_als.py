"""CP-ALS behaviour: fit improvement, exact recovery, backend equivalence."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro import core


def _norm(t):
    return float(np.linalg.norm(t.values))


def test_fit_monotone_improvement():
    t = core.random_tensor((40, 25, 30), 2000, seed=3, dist="powerlaw")
    b = core.build_blco(t)
    res = core.cp_als(lambda f, m: core.mttkrp(b, f, m), t.dims, 8,
                      norm_x=_norm(t), iters=10, seed=1)
    fits = res.fits
    assert fits[-1] > fits[0]
    # ALS fit is non-decreasing up to fp noise
    assert all(b2 >= a - 1e-3 for a, b2 in zip(fits, fits[1:]))


def test_exact_low_rank_recovery():
    """A synthetic rank-3 tensor must be fit to ~1.0 by rank-8 CP-ALS."""
    rng = np.random.default_rng(0)
    dims, r0 = (20, 16, 12), 3
    factors = [rng.standard_normal((d, r0)) for d in dims]
    dense = np.einsum("ir,jr,kr->ijk", *factors)
    idx = np.argwhere(np.abs(dense) > 0.5)          # sparsify
    vals = dense[tuple(idx.T)].astype(np.float32)
    t = core.from_coo(idx, vals, dims)
    b = core.build_blco(t)
    res = core.cp_als(lambda f, m: core.mttkrp(b, f, m), t.dims, 8,
                      norm_x=_norm(t), iters=60, seed=2, tol=1e-9)
    # the sampled tensor is not exactly low-rank, but fit must be high
    assert res.fits[-1] > 0.5, res.fits[-5:]


def test_backends_reach_same_fit():
    t = core.random_tensor((25, 18, 21), 1200, seed=4, dist="powerlaw")
    b = core.build_blco(t)
    coo = core.COOFormat.build(t)
    fits = {}
    for name, fn in [
        ("blco", lambda f, m: core.mttkrp(b, f, m)),
        ("coo", lambda f, m: core.coo_mttkrp(coo, f, m)),
    ]:
        res = core.cp_als(fn, t.dims, 6, norm_x=_norm(t), iters=8, seed=5)
        fits[name] = res.fits[-1]
    assert abs(fits["blco"] - fits["coo"]) < 1e-3, fits


def test_streaming_cp_als_matches_in_memory():
    t = core.random_tensor((30, 22, 14), 1500, seed=6, dist="powerlaw")
    b = core.build_blco(t, max_nnz_per_block=256)   # force multiple launches
    ex = core.OOMExecutor(b, queues=3)
    r1 = core.cp_als(lambda f, m: core.mttkrp(b, f, m), t.dims, 6,
                     norm_x=_norm(t), iters=5, seed=7)
    r2 = core.cp_als(lambda f, m: ex.mttkrp(f, m), t.dims, 6,
                     norm_x=_norm(t), iters=5, seed=7)
    np.testing.assert_allclose(r1.fits, r2.fits, rtol=1e-4, atol=1e-4)
    assert ex.stats.launches > 0 and ex.stats.h2d_bytes > 0


def test_reconstruction_shrinks_residual():
    t = core.random_tensor((15, 12, 10), 600, seed=8, dist="clustered")
    b = core.build_blco(t)
    res = core.cp_als(lambda f, m: core.mttkrp(b, f, m), t.dims, 10,
                      norm_x=_norm(t), iters=30, seed=9)
    dense = t.to_dense()
    recon = core.reconstruct_dense(res)
    resid = np.linalg.norm(dense - recon) / np.linalg.norm(dense)
    assert resid < 0.9
    assert abs((1 - resid) - res.fits[-1]) < 0.05   # fit formula consistency


@pytest.mark.parametrize("cond", [1.0, 1e3, 1e6])
def test_gram_solve_matches_float64_pseudo_inverse(cond):
    """The mode update's solve against a positive definite Hadamard Gram
    product agrees with a float64 ``M pinv(V)`` to float32 rounding times
    the condition."""
    from repro.core.cp_als import gram_solve
    rng = np.random.default_rng(0)
    q, _ = np.linalg.qr(rng.standard_normal((16, 16)))
    v = (q * np.geomspace(1.0, 1.0 / cond, 16)) @ q.T
    m = rng.standard_normal((50, 16))
    want = m @ np.linalg.pinv(v)
    got = np.asarray(gram_solve(jnp.asarray(m, jnp.float32),
                                jnp.asarray(v, jnp.float32)), np.float64)
    err = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert err < 1e-5 * cond, err


def test_gram_solve_singular_falls_back_to_pseudo_inverse():
    """A singular ``V`` (two equal rank columns) has no Cholesky factor;
    the update is then ``M pinv(V)``, finite."""
    from repro.core.cp_als import gram_solve
    rng = np.random.default_rng(1)
    a = rng.standard_normal((6, 4)).astype(np.float32)
    a[:, 3] = a[:, 2]
    v = jnp.asarray(a.T @ a)
    m = jnp.asarray(rng.standard_normal((9, 4)).astype(np.float32))
    got = np.asarray(gram_solve(m, v))
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, np.asarray(m @ jnp.linalg.pinv(v)),
                               rtol=1e-4, atol=1e-4)
