"""The benchmark's tensor generator: a FROSTT shape at an exact nnz from a seed.

A copy of the program's ``core.tensor.random_tensor_exact`` distribution,
kept here so that no later change to the program can move the yardstick:

* ``nnz`` coordinate draws, each mode i.i.d. ``min(Zipf(1.3) - 1, I_m - 1)``
  (a power-law head: a few dense fibers, as in NELL-2);
* duplicates removed, then topped up with uniform draws over the whole
  index space until exactly ``nnz`` distinct coordinates remain;
* values standard normal float32, exact zeros replaced by 1.

Zipf draws are made by Walker's alias method over the clipped distribution
(exact probabilities from the Hurwitz zeta function), which is several
times faster than ``Generator.zipf`` and gives the same distribution.
Coordinates come out in row-major order, as in a FROSTT ``.tns`` file.
"""
from __future__ import annotations

import numpy as np
from scipy.special import zeta

ZIPF_A = 1.3


def clipped_zipf_probs(d: int, a: float = ZIPF_A) -> np.ndarray:
    """P(coordinate = k), k < d, of ``min(Zipf(a) - 1, d - 1)``."""
    head = np.arange(1, d, dtype=np.float64) ** -a      # Zipf values 1..d-1
    tail = zeta(a, d)                                   # sum_{z >= d} z^-a
    p = np.append(head, tail)
    return p / p.sum()


def alias_table(p: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Vose's alias table: draw ``k`` uniform, keep it with ``prob[k]``,
    else take ``alias[k]``."""
    n = len(p)
    prob = p * n
    alias = np.arange(n)
    small = [i for i in range(n) if prob[i] < 1.0]
    large = [i for i in range(n) if prob[i] >= 1.0]
    while small and large:
        s, g = small.pop(), large.pop()
        alias[s] = g
        prob[g] -= 1.0 - prob[s]
        (small if prob[g] < 1.0 else large).append(g)
    for i in small + large:
        prob[i] = 1.0
    return prob, alias


def draw_powerlaw(rng, d: int, n: int) -> np.ndarray:
    prob, alias = alias_table(clipped_zipf_probs(d))
    k = rng.integers(0, d, size=n)
    return np.where(rng.random(n) < prob[k], k, alias[k])


def row_major_keys(dims, coords) -> np.ndarray:
    keys = np.zeros(len(coords[0]), np.int64)
    for d, c in zip(dims, coords):
        keys *= d
        keys += c
    return keys


def frostt_tensor(dims, nnz: int, seed: int):
    """``(indices (nnz, N) int64, values (nnz,) float32)``: exactly ``nnz``
    distinct coordinates, from ``seed`` alone."""
    dims = tuple(int(d) for d in dims)
    size = int(np.prod([float(d) for d in dims]))
    if size >= 2 ** 62 or nnz > size:
        raise ValueError(f"dims {dims} cannot hold {nnz} distinct int64 keys")
    rng = np.random.default_rng(seed)
    keys = np.unique(row_major_keys(
        dims, [draw_powerlaw(rng, d, nnz) for d in dims]))
    while len(keys) < nnz:
        new = np.unique(rng.integers(0, size, size=nnz - len(keys)))
        pos = np.minimum(np.searchsorted(keys, new), len(keys) - 1)
        new = new[keys[pos] != new]
        keys = np.sort(np.concatenate([keys, new]))
    indices = np.stack(np.unravel_index(keys, dims), axis=1).astype(np.int64)
    values = rng.standard_normal(nnz, dtype=np.float32)
    values[values == 0] = 1
    return indices, values
