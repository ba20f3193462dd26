"""Host-side sparse tensor container (COO) + synthetic generators + FROSTT IO.

The paper evaluates 14 real-world FROSTT/HaTen2 tensors. Offline we synthesize
tensors that reproduce the *structural* properties the paper's analysis keys on
(fiber density, mode-length skew, hypersparsity); a ``.tns`` loader is provided
for the real data sets when available.
"""
from __future__ import annotations

import dataclasses
import numpy as np


@dataclasses.dataclass(frozen=True)
class SparseTensor:
    """An N-order sparse tensor in coordinate (COO) form, host resident.

    indices: (nnz, N) int64, 0-based coordinates, deduplicated.
    values:  (nnz,) float32/float64.
    dims:    mode lengths.
    """
    dims: tuple[int, ...]
    indices: np.ndarray
    values: np.ndarray

    def __post_init__(self):
        assert self.indices.ndim == 2 and self.indices.shape[1] == len(self.dims)
        assert self.values.shape == (self.indices.shape[0],)
        assert self.indices.dtype == np.int64

    @property
    def order(self) -> int:
        return len(self.dims)

    @property
    def nnz(self) -> int:
        return self.indices.shape[0]

    @property
    def density(self) -> float:
        size = float(np.prod([float(d) for d in self.dims]))
        return self.nnz / size

    def to_dense(self) -> np.ndarray:
        """Dense materialization — test oracle only (small tensors)."""
        dense = np.zeros(self.dims, dtype=self.values.dtype)
        dense[tuple(self.indices.T)] += self.values
        return dense

    def matricize(self, mode: int) -> np.ndarray:
        """Mode-n matricization X_(n) as a dense matrix — test oracle only."""
        dense = self.to_dense()
        perm = (mode,) + tuple(m for m in range(self.order) if m != mode)
        return dense.transpose(perm).reshape(self.dims[mode], -1)


def _dedupe(indices: np.ndarray, values: np.ndarray, dims) -> SparseTensor:
    # Lexicographic dedupe, summing duplicate values (standard COO semantics).
    order = np.lexsort(indices.T[::-1])
    indices = indices[order]
    values = values[order]
    keep = np.ones(len(values), dtype=bool)
    if len(values) > 1:
        same = np.all(indices[1:] == indices[:-1], axis=1)
        keep[1:] = ~same
    # sum duplicates into the kept representative
    group = np.cumsum(keep) - 1
    out_vals = np.zeros(int(group[-1]) + 1 if len(values) else 0, dtype=values.dtype)
    np.add.at(out_vals, group, values)
    return SparseTensor(tuple(int(d) for d in dims), indices[keep], out_vals)


def random_tensor(dims, nnz, *, seed=0, dtype=np.float32, dist="uniform") -> SparseTensor:
    """Synthetic sparse tensor.

    dist="uniform":   coordinates i.i.d. uniform (models hypersparse FROSTT sets
                      like Flickr/Delicious — density 1e-14).
    dist="powerlaw":  per-mode Zipf-distributed coordinates → dense fibers for a
                      few indices (models NELL-2 / Reddit fiber-density skew,
                      which drives the paper's conflict-resolution behavior).
    dist="clustered": coordinates drawn inside a few random sub-boxes (models
                      the block structure HiCOO exploits; stresses ALTO ordering).
    """
    rng = np.random.default_rng(seed)
    dims = tuple(int(d) for d in dims)
    n = len(dims)
    nnz = int(nnz)
    idx = np.empty((nnz, n), dtype=np.int64)
    if dist in ("uniform", "powerlaw"):
        for m, d in enumerate(dims):
            idx[:, m] = _draw_mode(rng, d, nnz, dist)
    elif dist == "clustered":
        k = max(1, min(8, min(dims) // 2))
        centers = np.stack([rng.integers(0, d, size=k) for d in dims], axis=1)
        box = [max(1, d // 8) for d in dims]
        pick = rng.integers(0, k, size=nnz)
        for m, d in enumerate(dims):
            off = rng.integers(0, box[m], size=nnz)
            idx[:, m] = np.minimum(centers[pick, m] + off, d - 1)
    else:
        raise ValueError(f"unknown dist {dist!r}")
    vals = rng.standard_normal(nnz).astype(dtype)
    # avoid exact zeros (degenerate nnz)
    vals = np.where(vals == 0, np.asarray(1.0, dtype), vals).astype(dtype)
    return _dedupe(idx, vals, dims)


def _draw_mode(rng, d: int, nnz: int, dist: str) -> np.ndarray:
    """``nnz`` coordinates of one mode of length ``d``."""
    if dist == "uniform":
        return rng.integers(0, d, size=nnz)
    # Zipf over the mode, clipped to the mode length
    z = np.minimum(rng.zipf(1.3, size=nnz) - 1, d - 1)
    rng.shuffle(z)  # decorrelate rank across modes
    return z


def _draw_keys(rng, dims, nnz: int, dist: str) -> np.ndarray:
    """Row-major linear keys of ``nnz`` coordinate draws."""
    keys = np.zeros(nnz, np.int64)
    for d in dims:
        keys *= d
        keys += _draw_mode(rng, d, nnz, dist)
    return keys


def random_tensor_exact(dims, nnz, *, seed=0, dtype=np.float32,
                        dist="powerlaw") -> SparseTensor:
    """Synthetic tensor with exactly ``nnz`` distinct coordinates.

    ``nnz`` draws from ``dist`` (as in :func:`random_tensor`), deduplicated,
    then topped up with uniform draws until ``nnz`` distinct coordinates
    remain: a skewed head over a uniform tail.  Power-law draws repeat
    heavily (at NELL-2 dims about four in five of 77M draws are repeats),
    so without the top-up a published nnz cannot be reached.  Works on
    int64 linear keys, O(nnz) host memory, and needs ``prod(dims) < 2^63``.
    """
    rng = np.random.default_rng(seed)
    dims = tuple(int(d) for d in dims)
    nnz = int(nnz)
    if float(np.prod([float(d) for d in dims])) >= 2.0 ** 63:
        raise ValueError(f"dims {dims} exceed int64 linear keys")
    keys = np.unique(_draw_keys(rng, dims, nnz, dist))
    while len(keys) < nnz:
        keys = np.union1d(keys, _draw_keys(rng, dims, nnz - len(keys),
                                           "uniform"))
    indices = np.stack(np.unravel_index(keys, dims), axis=1).astype(np.int64)
    vals = rng.standard_normal(nnz).astype(dtype)
    vals[vals == 0] = 1
    return SparseTensor(dims, indices, vals)


def from_coo(indices, values, dims) -> SparseTensor:
    return _dedupe(np.asarray(indices, np.int64), np.asarray(values), dims)


def load_tns(path: str, dtype=np.float64) -> SparseTensor:
    """FROSTT ``.tns`` loader: one nnz per line, 1-based indices then value."""
    raw = np.loadtxt(path, dtype=np.float64, ndmin=2)
    idx = raw[:, :-1].astype(np.int64) - 1
    vals = raw[:, -1].astype(dtype)
    dims = tuple(int(d) for d in idx.max(axis=0) + 1)
    return _dedupe(idx, vals, dims)


# Shapes/nnz modeled on Table 2 of the paper, scaled for CPU-offline runs.
PAPER_LIKE_SUITE = {
    # name: (dims, nnz, dist) — scaled ~1000x down, preserving mode-length skew.
    "nips-like":   ((256, 256, 1024, 16), 30_000, "uniform"),
    "uber-like":   ((183, 24, 1140, 1717), 33_000, "powerlaw"),
    "chicago-like": ((620, 24, 77, 32), 53_000, "powerlaw"),
    "vast-like":   ((16384, 1024, 2), 26_000, "uniform"),
    "darpa-like":  ((2048, 2048, 65536), 28_000, "powerlaw"),
    "nell2-like":  ((1210, 920, 2880), 76_000, "powerlaw"),
    "fb-like":     ((262144, 262144, 166), 10_000, "uniform"),
    "deli-like":   ((8192, 65536, 32768, 1400), 14_000, "uniform"),
    "amazon-like": ((65536, 16384, 16384), 170_000, "powerlaw"),
}


def paper_like(name: str, *, seed=0, dtype=np.float32) -> SparseTensor:
    dims, nnz, dist = PAPER_LIKE_SUITE[name]
    return random_tensor(dims, nnz, seed=seed, dtype=dtype, dist=dist)


# Published dims and nnz of FROSTT tensors (paper Table 2), unscaled.
FROSTT_SUITE = {
    # name: (dims, nnz, dist)
    "nell-2": ((12092, 9184, 28818), 76_879_419, "powerlaw"),
}


def frostt_like(name: str, *, seed=0, nnz: int | None = None,
                dtype=np.float32) -> SparseTensor:
    """A FROSTT tensor's shape at its published dims and exactly its nnz
    (or ``nnz``, a cut), generated from ``seed``."""
    dims, full, dist = FROSTT_SUITE[name]
    return random_tensor_exact(dims, full if nnz is None else nnz, seed=seed,
                               dtype=dtype, dist=dist)
