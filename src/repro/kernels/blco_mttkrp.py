"""Pallas kernels: the BLCO MTTKRP computing phase (paper §5.1.2, §5.2).

Two variants, mirroring the paper's two conflict-resolution mechanisms, both
re-thought for the TPU memory hierarchy (DESIGN.md §2):

``segment`` (register-based analogue, paper §5.2)
    Per VMEM tile of T non-zeros: partial = value x hadamard(gathered rows);
    segment boundaries discovered on the fly by comparing adjacent target
    indices; the segmented reduction is performed as **one-hot @ partials on
    the MXU** — the systolic array plays the role of the GPU's warp shuffles.
    Output: per-tile compressed (seg_tgt, seg_sums); the caller issues ONE
    update per discovered segment (vs per nnz), the paper's atomic reduction.

``stash`` (hierarchical, paper §5.1 steps 5-7)
    For short target modes (the §5.3 contention regime) the entire (I, R)
    output lives in VMEM as a revisited output block; every grid step
    accumulates its tile directly via a (I x T) one-hot matmul. The TPU grid
    is sequential on a core, so the revisited block is the local-memory
    stash; the C partial copies + final merge happen across cores at the XLA
    level (see ops.py / core.mttkrp hierarchical path).

No scatter, no atomics, no mode-specific data — one kernel for every mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .delinearize import interpret_mode


def _hadamard(vals, gathered):
    partial = vals[:, None].astype(jnp.result_type(vals, gathered[0]))
    for u in gathered:
        partial = partial * u
    return partial


def _onthefly_segments(tgt):
    """Segment ids within a tile: boundary at row 0 and wherever tgt changes."""
    t = tgt.shape[0]
    pos = jax.lax.broadcasted_iota(jnp.int32, (t,), 0)
    prev = jnp.roll(tgt, 1)
    flags = jnp.where((pos == 0) | (tgt != prev), 1, 0).astype(jnp.int32)
    return jnp.cumsum(flags) - 1        # (t,), values in [0, #segments)


def _segment_kernel(vals_ref, tgt_ref, *rest):
    *g_refs, seg_tgt_ref, seg_sums_ref = rest
    vals = vals_ref[...]
    tgt = tgt_ref[...]
    t = vals.shape[0]
    partial = _hadamard(vals, [g[...] for g in g_refs])

    seg_id = _onthefly_segments(tgt)
    # one-hot segmented reduction on the MXU: [T, T] @ [T, R]
    rows = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    onehot = (rows == seg_id[None, :]).astype(partial.dtype)
    seg_sums_ref[...] = jax.lax.dot(onehot, partial,
                                    preferred_element_type=partial.dtype)
    # segment target index; padding rows (no segment) -> -1
    seg_tgt = jnp.max(jnp.where(rows == seg_id[None, :], tgt[None, :] + 1, 0),
                      axis=1) - 1
    seg_tgt_ref[...] = seg_tgt


@functools.partial(jax.jit, static_argnames=("tile",))
def mttkrp_segments(vals, tgt, gathered, *, tile: int = 256):
    """Fused hadamard + on-the-fly segmented reduction, per VMEM tile.

    vals: (T,) float; tgt: (T,) int32 (ALTO order, NOT sorted); gathered:
    tuple of (T, R) non-target factor rows. T % tile == 0.
    Returns (seg_tgt (T,) int32 [-1 padded], seg_sums (T, R)).
    """
    t = vals.shape[0]
    r = gathered[0].shape[1]
    assert t % tile == 0, (t, tile)
    grid = (t // tile,)
    vec = pl.BlockSpec((tile,), lambda i: (i,))
    mat = pl.BlockSpec((tile, r), lambda i: (i, 0))
    seg_tgt, seg_sums = pl.pallas_call(
        _segment_kernel,
        grid=grid,
        in_specs=[vec, vec] + [mat] * len(gathered),
        out_specs=(vec, mat),
        out_shape=(jax.ShapeDtypeStruct((t,), jnp.int32),
                   jax.ShapeDtypeStruct((t, r), jnp.result_type(vals, gathered[0]))),
        interpret=interpret_mode(),
    )(vals, tgt, *gathered)
    return seg_tgt, seg_sums


def _stash_kernel(vals_ref, tgt_ref, *rest, out_rows):
    *g_refs, out_ref = rest
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    vals = vals_ref[...]
    tgt = tgt_ref[...]
    t = vals.shape[0]
    partial = _hadamard(vals, [g[...] for g in g_refs])
    # direct (I x T) one-hot accumulation into the VMEM-resident stash
    rows = jax.lax.broadcasted_iota(jnp.int32, (out_rows, t), 0)
    onehot = (rows == tgt[None, :]).astype(partial.dtype)
    out_ref[...] += jax.lax.dot(onehot, partial,
                                preferred_element_type=partial.dtype)


@functools.partial(jax.jit, static_argnames=("out_rows", "tile"))
def mttkrp_stash(vals, tgt, gathered, *, out_rows: int, tile: int = 256):
    """Hierarchical small-mode variant: full (out_rows, R) accumulated in a
    revisited VMEM output block across the sequential TPU grid.

    Only for short target modes (out_rows <= ~1024) per the §5.3 heuristic —
    the stash must fit VMEM alongside the tile.
    """
    t = vals.shape[0]
    r = gathered[0].shape[1]
    assert t % tile == 0, (t, tile)
    grid = (t // tile,)
    vec = pl.BlockSpec((tile,), lambda i: (i,))
    mat = pl.BlockSpec((tile, r), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_stash_kernel, out_rows=out_rows),
        grid=grid,
        in_specs=[vec, vec] + [mat] * len(gathered),
        out_specs=pl.BlockSpec((out_rows, r), lambda i: (0, 0)),  # revisited
        out_shape=jax.ShapeDtypeStruct((out_rows, r),
                                   jnp.result_type(vals, gathered[0])),
        interpret=interpret_mode(),
    )(vals, tgt, *gathered)
