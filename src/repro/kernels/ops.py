"""Public wrappers around the Pallas BLCO-MTTKRP kernels.

``pallas_mttkrp`` is a drop-in replacement for ``repro.core.mttkrp.mttkrp``:
same BLCOTensor in, same (I_mode, R) out, validated against the same dense
oracle.  It is driven by the device-resident launch cache
(``repro.core.launches.LaunchCache``) and executes the ENTIRE pipeline —
delinearize -> factor-row gather -> hadamard -> on-the-fly segmented
reduction — as one fused ``pallas_call`` per tile inside a single jitted
dispatch (``repro.kernels.fused``): zero per-call host padding, no
HBM-materialized intermediates.

``pallas_mttkrp_phases`` keeps the PR-2 three-phase pipeline (standalone
delinearize kernel -> XLA gather -> compute kernel, each phase round-
tripping through HBM) as the benchmark reference the fused path is
measured against in ``BENCH_3.json``.  It too is cache-driven — the host
numpy padding it used to redo every call is gone.
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from repro.core.blco import BLCOTensor
from repro.core.counters import record_dispatch
from repro.core.mttkrp import choose_resolution, launch_cache_for

from .delinearize import delinearize
from .blco_mttkrp import mttkrp_segments, mttkrp_stash
from .fused import (STASH_MAX_ROWS, fused_cache_mttkrp, fused_mttkrp_flat)
from .ref import scatter_segments_ref


def pallas_mttkrp(blco: BLCOTensor, factors, mode: int, *,
                  tile: int = 256, resolution: str = "auto", cache=None):
    """Full mode-n MTTKRP, fused single-dispatch Pallas path.

    The launch cache is built once (attached to ``blco``, or passed in);
    every call afterwards is one jitted dispatch over the cached stream.
    """
    assert 0 <= mode < blco.order
    cache = cache if cache is not None else launch_cache_for(blco)
    return fused_cache_mttkrp(cache, factors, mode, resolution=resolution,
                              tile=tile)


def pallas_mttkrp_phases(blco: BLCOTensor, factors, mode: int, *,
                         tile: int = 256, resolution: str = "auto",
                         cache=None):
    """The PR-2 three-phase Pallas pipeline (benchmark reference).

    Per call: delinearize kernel -> HBM coords -> XLA gather -> HBM rows ->
    compute kernel -> per-segment scatter.  Cache-driven (no host numpy),
    but the intermediates still round-trip through device memory and the
    phases are separate dispatches — exactly what the fused path removes.
    """
    assert 0 <= mode < blco.order
    cache = cache if cache is not None else launch_cache_for(blco)
    factors = tuple(jnp.asarray(f) for f in factors)
    rank = factors[0].shape[1]
    if resolution == "auto":
        resolution = choose_resolution(blco.dims[mode])
    use_stash = (resolution == "hierarchical"
                 and blco.dims[mode] <= STASH_MAX_ROWS)
    if cache.num_launches == 0:
        return jnp.zeros((blco.dims[mode], rank),
                         jnp.result_type(cache.vals, factors[0]))

    hi, lo, vals, bases = cache.flat()
    t = int(hi.shape[0])
    tile = math.gcd(t, max(1, min(tile, t)))   # largest dividing tile
    record_dispatch(3)          # three separate device phases per call

    # phase 1: processing (standalone Pallas delinearize kernel)
    coords = delinearize(hi, lo, bases, field_bits=cache.re_fields,
                         field_shifts=cache.re_shifts, tile=tile)
    # phase 2: gather non-target rows (XLA native gather, HBM round-trip)
    gathered = tuple(jnp.take(factors[m], coords[:, m], axis=0)
                     for m in range(blco.order) if m != mode)
    tgt = coords[:, mode]

    # phase 3: computing (Pallas kernel) + final update
    if use_stash:
        return mttkrp_stash(vals, tgt, gathered, out_rows=blco.dims[mode],
                            tile=tile)
    seg_tgt, seg_sums = mttkrp_segments(vals, tgt, gathered, tile=tile)
    return scatter_segments_ref(seg_tgt, seg_sums, blco.dims[mode])


__all__ = ["pallas_mttkrp", "pallas_mttkrp_phases", "fused_mttkrp_flat",
           "fused_cache_mttkrp"]
