"""The benchmark's tensor generator (CPU, seconds)."""
from __future__ import annotations

import numpy as np
import pytest

from bench import gen


@pytest.mark.parametrize("dims,nnz", [((12092, 9184, 28818), 200_000),
                                      ((6186, 24, 77, 32), 300_000),
                                      ((7, 5, 3), 105)])
def test_exact_distinct_nnz_in_bounds(dims, nnz):
    idx, vals = gen.frostt_tensor(dims, nnz, 2 ** 40 + 3)
    assert idx.shape == (nnz, len(dims)) and idx.dtype == np.int64
    assert vals.shape == (nnz,) and vals.dtype == np.float32
    assert np.all(vals != 0)
    assert np.all(idx >= 0) and np.all(idx < np.array(dims))
    keys = gen.row_major_keys(dims, idx.T)
    assert len(np.unique(keys)) == nnz
    assert np.all(np.diff(keys) > 0)          # row-major order, as a .tns


def test_same_seed_same_tensor():
    a = gen.frostt_tensor((6186, 24, 77, 32), 50_000, 2 ** 35 + 11)
    b = gen.frostt_tensor((6186, 24, 77, 32), 50_000, 2 ** 35 + 11)
    c = gen.frostt_tensor((6186, 24, 77, 32), 50_000, 2 ** 35 + 12)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])


@pytest.mark.parametrize("d", [24, 77, 12092])
def test_alias_table_is_the_clipped_zipf(d):
    p = gen.clipped_zipf_probs(d)
    assert p.sum() == pytest.approx(1.0)
    # the same law as numpy's own Zipf draws, clipped
    assert p[0] == pytest.approx(1 / 3.9319492, rel=1e-6)   # 1 / zeta(1.3)
    prob, alias = gen.alias_table(p)
    # P(k) = (prob[k] + sum of (1 - prob[j]) over j aliased to k) / d
    back = prob.copy()
    np.add.at(back, alias, 1.0 - prob)
    np.testing.assert_allclose(back / d, p, rtol=1e-9, atol=1e-15)


def test_draws_match_numpy_zipf():
    rng = np.random.default_rng(5)
    n = 400_000
    ours = np.bincount(gen.draw_powerlaw(rng, 24, n), minlength=24) / n
    theirs = np.bincount(np.minimum(rng.zipf(1.3, n) - 1, 23),
                         minlength=24) / n
    np.testing.assert_allclose(ours, theirs, atol=4e-3)
