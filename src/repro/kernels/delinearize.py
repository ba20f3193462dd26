"""Pallas kernel: BLCO de-linearization (the paper's processing phase, §5.1.1).

Each grid step loads a VMEM tile of stored (hi, lo) uint32 index words and the
per-element block bases, and recovers every mode's coordinate with the
shift+mask extraction the BLCO re-encoding was designed for — 32-bit ops only
(TPU VPU is a 32-bit machine; DESIGN.md §2). Each coordinate is computed
independently, exposing ILP exactly as the paper notes.

Fields that straddle the 32-bit word boundary are stitched from both words —
the price of the 2x-uint32 adaptation, two extra bitwise ops for at most one
mode per tensor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def interpret_mode() -> bool:
    """Whether Pallas kernels run in the interpreter: exactly off the TPU.

    Derived from the platform, never chosen by a caller, so a TPU never
    times the interpreter and a backend without Mosaic still validates the
    kernels.  Every kernel of this package asks here.
    """
    return jax.default_backend() != "tpu"


def extract_field_words(hi, lo, shift: int, width: int):
    """One mode's field from (hi, lo) uint32 word pairs — 32-bit ops only.

    Shared by the standalone delinearize kernel and the fused MTTKRP
    pipeline (``repro.kernels.fused``); shift/width are static per mode.
    """
    if width == 0:
        return jnp.zeros_like(lo)
    if shift >= 32:                        # entirely in hi word
        mask = jnp.uint32((1 << width) - 1) if width < 32 else jnp.uint32(0xFFFFFFFF)
        return (hi >> jnp.uint32(shift - 32)) & mask
    if shift + width <= 32:                # entirely in lo word
        mask = jnp.uint32((1 << width) - 1) if width < 32 else jnp.uint32(0xFFFFFFFF)
        return (lo >> jnp.uint32(shift)) & mask
    # straddles: stitch both words
    lo_bits = 32 - shift
    lo_part = lo >> jnp.uint32(shift)
    hi_part = hi & jnp.uint32((1 << (shift + width - 32)) - 1)
    field = lo_part | (hi_part << jnp.uint32(lo_bits))
    return field & jnp.uint32((1 << width) - 1)


def _kernel(hi_ref, lo_ref, bases_ref, out_ref, *, field_bits, field_shifts):
    hi = hi_ref[...]
    lo = lo_ref[...]
    cols = []
    for n, (shift, width) in enumerate(zip(field_shifts, field_bits)):
        field = extract_field_words(hi, lo, shift, width)
        cols.append(field.astype(jnp.int32) + bases_ref[:, n])
    out_ref[...] = jnp.stack(cols, axis=1)


@functools.partial(jax.jit,
                   static_argnames=("field_bits", "field_shifts", "tile"))
def delinearize(idx_hi, idx_lo, bases, *, field_bits: tuple,
                field_shifts: tuple, tile: int = 1024):
    """(T,) uint32 words + (T, N) int32 bases -> (T, N) int32 coordinates.

    T must be a multiple of ``tile`` (callers pad launches to power-of-two
    sizes already).
    """
    t = idx_hi.shape[0]
    n_modes = len(field_bits)
    assert t % tile == 0, (t, tile)
    grid = (t // tile,)
    return pl.pallas_call(
        functools.partial(_kernel, field_bits=field_bits,
                          field_shifts=field_shifts),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile,), lambda i: (i,)),
            pl.BlockSpec((tile,), lambda i: (i,)),
            pl.BlockSpec((tile, n_modes), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((tile, n_modes), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, n_modes), jnp.int32),
        interpret=interpret_mode(),
    )(idx_hi, idx_lo, bases)
