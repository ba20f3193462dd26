"""Fused single-``pallas_call`` BLCO MTTKRP: the whole per-tile pipeline.

PR 2 ran the Pallas path as THREE device round-trips per launch —
delinearize kernel -> HBM coords -> XLA gather -> HBM gathered rows ->
compute kernel.  Here the paper's two phases fuse into ONE kernel per tile:

  per VMEM tile of T non-zeros:
    1. processing (§5.1.1): shift+mask de-linearization of the (hi, lo)
       stored index words + per-element block bases — in registers;
    2. computing (§5.1.2): gather the non-target factor rows from the
       VMEM-resident factor matrices, hadamard with the values, discover
       segment boundaries on the fly, and segment-reduce with a one-hot
       matmul on the MXU.

Coordinates and gathered rows never touch HBM.  Two conflict-resolution
variants, as in the paper:

``segment`` (register analogue, §5.2): per-tile compressed (seg_tgt,
    seg_sums) outputs; ONE update per discovered segment is applied by a
    masked scatter-add that XLA fuses into the same dispatch.
``stash`` (hierarchical, §5.1 steps 5-7): the full (I, R) output lives in
    VMEM as a revisited block accumulated across the sequential TPU grid —
    for short target modes (the §5.3 contention regime).

Inputs come straight from the device-resident launch cache
(``repro.core.launches.LaunchCache.flat()``): no per-call host padding, and
the host issues exactly ONE jitted dispatch per MTTKRP call.

The factor matrices are passed as whole revisited blocks, so on TPU they
must fit VMEM alongside one tile — the same constraint the paper's
shared-memory gather stage has.

Mosaic does not compile this kernel: the in-kernel factor-row gather
(``jnp.take`` of a ``(T,)`` index vector from an ``(I, R)`` factor block)
is refused with "Shape mismatch in input, indices and output", at every
width.  Making it compile needs the factors kept in HBM with the block's
row slices copied in by DMA, so until then the fused path runs only where
Pallas interprets and raises on a TPU (:func:`require_fused_support`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.counters import record_dispatch
from repro.core.mttkrp import CONTENTION_THRESHOLD, choose_resolution

from .delinearize import extract_field_words, interpret_mode

# VMEM budget for the stash variant: the revisited (out_rows, R) block must
# fit alongside one tile (same bound ops.py used for the 3-dispatch path).
STASH_MAX_ROWS = 4 * CONTENTION_THRESHOLD


def require_fused_support() -> None:
    """Raise where the fused kernel would have to compile for a TPU.

    Mosaic refuses its in-kernel row gather (see the module docstring), and
    running the interpreter on a TPU would time the interpreter, so the
    fused path is refused up front rather than failing or crawling later.
    """
    if not interpret_mode():
        raise NotImplementedError(
            "kernel='pallas' does not compile for TPU: Mosaic refuses the "
            "fused kernel's in-kernel factor-row gather ('Shape mismatch in "
            "input, indices and output'); use kernel='xla'")


def _delinearize_tile(hi, lo, bases, field_bits, field_shifts):
    """All mode coordinates of one tile, in registers. Returns list of (t,)."""
    coords = []
    for n, (shift, width) in enumerate(zip(field_shifts, field_bits)):
        field = extract_field_words(hi, lo, shift, width)
        coords.append(field.astype(jnp.int32) + bases[:, n])
    return coords


def _partial_and_target(hi_ref, lo_ref, vals_ref, bases_ref, f_refs, *,
                        mode, field_bits, field_shifts):
    """Phases 1+2 shared by both variants: delinearize, gather, hadamard."""
    hi = hi_ref[...]
    lo = lo_ref[...]
    vals = vals_ref[...]
    coords = _delinearize_tile(hi, lo, bases_ref[...], field_bits,
                               field_shifts)
    # promote, never downcast (dtype parity with the XLA scan path)
    partial = vals[:, None].astype(jnp.result_type(vals, f_refs[0]))
    j = 0
    for m in range(len(field_bits)):
        if m == mode:
            continue
        rows = jnp.take(f_refs[j][...], coords[m], axis=0)
        partial = partial * rows
        j += 1
    return partial, coords[mode]


def _fused_segment_kernel(hi_ref, lo_ref, vals_ref, bases_ref, *rest,
                          mode, field_bits, field_shifts):
    *f_refs, seg_tgt_ref, seg_sums_ref = rest
    partial, tgt = _partial_and_target(hi_ref, lo_ref, vals_ref, bases_ref,
                                       f_refs, mode=mode,
                                       field_bits=field_bits,
                                       field_shifts=field_shifts)
    t = tgt.shape[0]
    # on-the-fly segment ids: boundary at row 0 and wherever tgt changes
    pos = jax.lax.broadcasted_iota(jnp.int32, (t,), 0)
    prev = jnp.roll(tgt, 1)
    flags = jnp.where((pos == 0) | (tgt != prev), 1, 0).astype(jnp.int32)
    seg_id = jnp.cumsum(flags) - 1
    # one-hot segmented reduction on the MXU: [T, T] @ [T, R]
    rows = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    onehot = (rows == seg_id[None, :]).astype(partial.dtype)
    seg_sums_ref[...] = jax.lax.dot(onehot, partial,
                                    preferred_element_type=partial.dtype)
    # segment target index; padding rows (no segment) -> -1
    seg_tgt = jnp.max(jnp.where(rows == seg_id[None, :], tgt[None, :] + 1, 0),
                      axis=1) - 1
    seg_tgt_ref[...] = seg_tgt


def _fused_stash_kernel(hi_ref, lo_ref, vals_ref, bases_ref, *rest,
                        mode, field_bits, field_shifts, out_rows):
    *f_refs, out_ref = rest
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    partial, tgt = _partial_and_target(hi_ref, lo_ref, vals_ref, bases_ref,
                                       f_refs, mode=mode,
                                       field_bits=field_bits,
                                       field_shifts=field_shifts)
    t = tgt.shape[0]
    # direct (I x T) one-hot accumulation into the VMEM-resident stash
    rows = jax.lax.broadcasted_iota(jnp.int32, (out_rows, t), 0)
    onehot = (rows == tgt[None, :]).astype(partial.dtype)
    out_ref[...] += jax.lax.dot(onehot, partial,
                                preferred_element_type=partial.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("field_bits", "field_shifts", "mode", "out_rows",
                     "variant", "tile", "interpret"))
def _fused_flat(hi, lo, vals, bases, factors, *, field_bits: tuple,
                field_shifts: tuple, mode: int, out_rows: int, variant: str,
                tile: int, interpret: bool):
    """One jitted dispatch: fused pallas_call (+ fused per-segment scatter)."""
    t = hi.shape[0]
    n_modes = len(field_bits)
    others = tuple(factors[m] for m in range(n_modes) if m != mode)
    r = others[0].shape[1]
    out_dtype = jnp.result_type(vals, others[0])
    grid = (t // tile,)
    vec = pl.BlockSpec((tile,), lambda i: (i,))
    basespec = pl.BlockSpec((tile, n_modes), lambda i: (i, 0))
    # factor matrices ride along as whole revisited blocks (VMEM-resident)
    fspecs = [pl.BlockSpec(f.shape, lambda i: (0, 0)) for f in others]

    if variant == "stash":
        return pl.pallas_call(
            functools.partial(_fused_stash_kernel, mode=mode,
                              field_bits=field_bits,
                              field_shifts=field_shifts, out_rows=out_rows),
            grid=grid,
            in_specs=[vec, vec, vec, basespec] + fspecs,
            out_specs=pl.BlockSpec((out_rows, r), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((out_rows, r), out_dtype),
            interpret=interpret,
        )(hi, lo, vals, bases, *others)

    seg_tgt, seg_sums = pl.pallas_call(
        functools.partial(_fused_segment_kernel, mode=mode,
                          field_bits=field_bits, field_shifts=field_shifts),
        grid=grid,
        in_specs=[vec, vec, vec, basespec] + fspecs,
        out_specs=(vec, pl.BlockSpec((tile, r), lambda i: (i, 0))),
        out_shape=(jax.ShapeDtypeStruct((t,), jnp.int32),
                   jax.ShapeDtypeStruct((t, r), out_dtype)),
        interpret=interpret,
    )(hi, lo, vals, bases, *others)
    # ONE update per discovered segment (paper's atomic reduction), fused by
    # XLA into the same dispatch; -1 rows are padding
    out = jnp.zeros((out_rows, r), seg_sums.dtype)
    return out.at[jnp.maximum(seg_tgt, 0)].add(
        jnp.where(seg_tgt[:, None] >= 0, seg_sums, 0))


def _variant_for(resolution: str, out_rows: int) -> str:
    if resolution == "hierarchical" and out_rows <= STASH_MAX_ROWS:
        return "stash"
    return "segment"


def fused_mttkrp_flat(hi, lo, vals, bases, factors, *, field_bits: tuple,
                      field_shifts: tuple, mode: int, out_rows: int,
                      resolution: str = "auto", tile: int = 256):
    """Fused MTTKRP over a flat reservation-padded nnz stream.

    hi/lo: (T,) uint32; vals: (T,); bases: (T, N) int32; T is the padded
    stream length (launch boundaries are irrelevant: per-element bases carry
    the block offsets, and segments are discovered per tile).  Exactly one
    recorded dispatch.
    """
    require_fused_support()
    factors = tuple(jnp.asarray(f) for f in factors)
    if resolution == "auto":
        resolution = choose_resolution(out_rows)
    t = int(hi.shape[0])
    # largest tile <= the requested one that divides the stream (LANE-
    # multiple reservations keep this at the requested tile; odd custom
    # reservations degrade the tile rather than crash)
    tile = math.gcd(t, max(1, min(tile, t)))
    record_dispatch()
    return _fused_flat(hi, lo, vals, bases, factors, field_bits=field_bits,
                       field_shifts=field_shifts, mode=mode,
                       out_rows=out_rows,
                       variant=_variant_for(resolution, out_rows),
                       tile=tile, interpret=interpret_mode())


def fused_cache_mttkrp(cache, factors, mode: int, *,
                       resolution: str = "auto", tile: int = 256):
    """Fused MTTKRP straight from a device-resident ``LaunchCache``.

    Zero per-call host work: the cache's stacked ``(L, reservation)`` arrays
    are reshaped on device into one flat stream and tiled by the fused
    kernel — one dispatch per call regardless of launch count.
    """
    if cache.closed:
        raise RuntimeError("launch cache is closed")
    factors = tuple(jnp.asarray(f) for f in factors)
    if cache.num_launches == 0:
        rank = factors[0].shape[1]
        return jnp.zeros((cache.dims[mode], rank),
                         jnp.result_type(cache.vals, factors[0]))
    hi, lo, vals, bases = cache.flat()
    return fused_mttkrp_flat(hi, lo, vals, bases, factors,
                             field_bits=cache.re_fields,
                             field_shifts=cache.re_shifts, mode=mode,
                             out_rows=cache.dims[mode],
                             resolution=resolution, tile=tile)
