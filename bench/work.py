"""Compulsory work of one MTTKRP, and the chip's peaks.

The work is what any implementation must do for a mode-n MTTKRP of an
order-N tensor with ``nnz`` nonzeros at rank R: read each nonzero's value
and its coordinates packed at ``ceil(log2 I_m)`` bits per mode, read each
other factor once, write the output once, and make N multiplies-or-adds
per nonzero and rank column.  It reads only the dims, nnz, rank, value
dtype and mode, so every kernel, conflict resolution and regime is judged
against the same work, and no sound change can read over 100%.  Factors
are counted at 4 bytes per element (float32).
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

PEAKS = Path(__file__).with_name("peaks.json")
FACTOR_ITEMSIZE = 4


def coord_bytes(dims) -> int:
    """Bytes of one nonzero's packed coordinates."""
    return math.ceil(sum(math.ceil(math.log2(d)) for d in dims) / 8)


def mttkrp_bytes(dims, nnz: int, rank: int, value_dtype, mode: int) -> int:
    v = np.dtype(value_dtype).itemsize
    others = sum(d for m, d in enumerate(dims) if m != mode)
    return (nnz * (v + coord_bytes(dims))
            + FACTOR_ITEMSIZE * rank * others
            + FACTOR_ITEMSIZE * rank * dims[mode])


def mttkrp_flops(dims, nnz: int, rank: int, value_dtype, mode: int) -> int:
    return nnz * rank * len(dims)


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def least_seconds(dims, nnz: int, rank: int, value_dtype, mode: int,
                  peaks: dict) -> float:
    """The least time the chip could take: the larger of bytes over HBM
    bandwidth and operations over peak FLOP/s."""
    return max(
        mttkrp_bytes(dims, nnz, rank, value_dtype, mode)
        / peaks["hbm_bytes_per_s"],
        mttkrp_flops(dims, nnz, rank, value_dtype, mode)
        / peaks["flops_per_s"])
