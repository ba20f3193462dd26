"""Plain reference of one CP-ALS sweep, in float64 on the host.

Independent of the program: it reads the COO tensor that ``gen`` made from
the seed (never the program's BLCO encoding) and the factor matrices that
entered each MTTKRP call, the way a served model's reference reads the
prompt with its served tokens.

* ``mttkrp_cols``: the mode-n MTTKRP on a few rank columns, chunked over
  nnz (``chip_smoke.host_reference``, vectorised over the columns);
* ``mode_update``: Alg. 1 lines 3 and 5, ``A = M pinv(*_{m!=n} A_m^T A_m)``
  with unit-norm columns;
* ``fit``: ``1 - ||X - X_hat|| / ||X||`` from the last mode's MTTKRP.

The control a float32 program must beat is the reference at
``"bfloat16"``: every value and product rounded to bfloat16, sums exact.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import ml_dtypes
import numpy as np

CHUNK = 1 << 20
THREADS = min(4, os.cpu_count() or 1)


class COO:
    """The tensor as the reference reads it: one contiguous int32 column of
    coordinates per mode and float64 values."""

    def __init__(self, indices, values, dims):
        self.dims = tuple(int(d) for d in dims)
        self.coords = [np.ascontiguousarray(indices[:, m], dtype=np.int32)
                       for m in range(len(self.dims))]
        self.values = np.asarray(values, np.float64)

    @property
    def nnz(self) -> int:
        return len(self.values)


def _bf16(x):
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16) \
        .astype(np.float64)


class Precision:
    """How the reference rounds: ``round`` every value it keeps, ``mul``
    elementwise products, ``dot`` matrix products."""

    def __init__(self, name: str):
        self.name = name
        if name == "float64":
            self.round = lambda x: np.asarray(x, np.float64)
            self.mul = np.multiply
            self.dot = np.matmul
        elif name == "bfloat16":
            self.round = _bf16
            self.mul = lambda a, b: _bf16(_bf16(a) * _bf16(b))
            self.dot = lambda a, b: _bf16(_bf16(a) @ _bf16(b))
        else:
            raise ValueError(f"unknown precision {name!r}")


def mttkrp_cols(coo: COO, factors, mode: int, cols,
                precision: str = "float64") -> np.ndarray:
    """``(I_mode, len(cols))``: columns ``cols`` of the mode-``mode``
    MTTKRP of ``coo`` with ``factors``, summed over chunks of nonzeros in
    a few threads."""
    p = Precision(precision)
    f = [np.ascontiguousarray(p.round(np.asarray(a)[:, cols]))
         for a in factors]
    c = len(cols)
    rows = coo.dims[mode]

    def chunk(s):
        e = min(s + CHUNK, coo.nnz)
        w = np.repeat(p.round(coo.values[s:e])[:, None], c, axis=1)
        for m, idx in enumerate(coo.coords):
            if m != mode:
                w = p.mul(w, np.take(f[m], idx[s:e], axis=0))
        key = coo.coords[mode][s:e].astype(np.int64)[:, None] * c \
            + np.arange(c)
        return np.bincount(key.ravel(), weights=w.ravel(),
                           minlength=rows * c)

    with ThreadPoolExecutor(THREADS) as ex:
        parts = list(ex.map(chunk, range(0, coo.nnz, CHUNK)))
    return np.sum(parts, axis=0).reshape(rows, c)


def mttkrp_dirs(coo: COO, factors, mode: int, q,
                precision: str = "float64") -> np.ndarray:
    """``(I_mode, q.shape[1])``: the mode-``mode`` MTTKRP of ``coo`` times
    ``q`` (rank x k), through every rank column, summed over chunks of
    nonzeros in a few threads."""
    p = Precision(precision)
    f = [np.asarray(a) for a in factors]
    q = p.round(np.asarray(q, np.float64))
    k = q.shape[1]
    rows = coo.dims[mode]

    def chunk(s):
        e = min(s + CHUNK // 4, coo.nnz)
        w = p.round(coo.values[s:e])[:, None]
        for m, idx in enumerate(coo.coords):
            if m != mode:
                w = p.mul(w, p.round(np.take(f[m], idx[s:e], axis=0)))
        y = p.dot(w, q)
        key = coo.coords[mode][s:e].astype(np.int64)[:, None] * k \
            + np.arange(k)
        return np.bincount(key.ravel(), weights=y.ravel(),
                           minlength=rows * k)

    with ThreadPoolExecutor(THREADS) as ex:
        parts = list(ex.map(chunk, range(0, coo.nnz, CHUNK // 4)))
    return np.sum(parts, axis=0).reshape(rows, k)


def _gram_product(factors, skip: int | None, p: Precision) -> np.ndarray:
    rank = np.asarray(factors[0]).shape[1]
    v = np.ones((rank, rank))
    for m, a in enumerate(factors):
        if m != skip:
            a = p.round(a)
            v = p.round(v * p.dot(a.T, a))
    return v


def mode_update(m_mat, factors, mode: int,
                precision: str = "float64") -> np.ndarray:
    """The new mode-``mode`` factor from its MTTKRP ``m_mat`` and the
    factors that entered the call."""
    p = Precision(precision)
    v = _gram_product(factors, mode, p)
    a = p.dot(p.round(m_mat), p.round(np.linalg.pinv(v)))
    lam = p.round(np.linalg.norm(a, axis=0))
    lam = np.where(lam > 0, lam, 1.0)
    return p.round(a / lam)


def condition(factors, mode: int) -> float:
    """Condition number of the mode update's ``V``, the Hadamard product
    of the other factors' Grams, in float64."""
    return float(np.linalg.cond(
        _gram_product(factors, mode, Precision("float64"))))


def fit(norm_x: float, factors, lam, m_last,
        precision: str = "float64") -> tuple[float, float]:
    """CP fit of ``(lam, factors)``, and the scale of its rounding.

    ``m_last`` is the last mode's MTTKRP with the factors before that
    mode's update.  The fit is ``1 - sqrt(|X|^2 + |X_hat|^2 - 2<X, X_hat>)
    / |X|``; the scale, ``(|X|^2 + |X_hat|^2 + 2|<X, X_hat>|) / |X|^2``,
    is how much larger than ``|X|^2`` the terms that cancel are: about 1
    at the cells' sizes, larger where the model's components nearly cancel
    one another, which makes any float32 fit proportionally less exact.
    """
    p = Precision(precision)
    lam = p.round(lam)
    v = _gram_product(factors, None, p)
    est_sq = float(p.round(p.dot(lam, p.dot(v, lam))))
    inner = float(p.round(np.sum(p.mul(lam, p.round(np.sum(
        p.mul(m_last, factors[-1]), axis=0))))))
    resid_sq = max(norm_x ** 2 + est_sq - 2.0 * inner, 0.0)
    scale = (norm_x ** 2 + abs(est_sq) + 2.0 * abs(inner)) / norm_x ** 2
    return float(1.0 - np.sqrt(resid_sq) / norm_x), scale


def norm(values) -> float:
    return float(np.linalg.norm(np.asarray(values, np.float64)))
