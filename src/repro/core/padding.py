"""Shared launch-padding arithmetic (one home for the repo's three copies).

Every execution path pads launches to a fixed *reservation* so device
buffer shapes (and therefore compiled executables) are reused — the JAX
analogue of the paper's fixed per-queue memory reservations (§4.2).  Two
roundings are in deliberate use:

* ``next_pow2`` / ``pad_pow2`` — power-of-two buckets, so *different*
  tensors whose largest launches land in the same bucket share one
  compiled executable (the streaming regime's cross-tensor reuse);
* ``pad_multiple`` — round up to a lane/tile multiple only, for callers
  that pinned an explicit reservation and just need it tile-divisible;
* ``pad_bucket`` — geometric size classes (at most ``2 + 8·octaves``
  distinct values up to any bound), the in-memory regime's default
  reservation.  ``pad_multiple`` alone admits O(max_launch / LANE)
  distinct reservation shapes — and therefore that many jit cache
  entries for the stacked scan — which the trace-tier cache-churn audit
  (``repro.analysis.trace.cachekeys``) flags as unbounded in launch
  shape.  Size classes cap the executable count at O(log max_launch)
  while keeping padding waste ≤ 25% (vs up to 2x for pure pow2
  buckets).

``LANE`` is the TPU lane count: nnz buffers are kept at a multiple of it
so vector loads are aligned and every Pallas tile size that divides the
reservation also divides the total.

Launch sizes themselves are derived from the device here too: a launch
(one streaming queue reservation) takes at most ``1/LAUNCH_SHARE`` of the
device's memory (``default_launch_nnz``), and the XLA scan path cuts each
reservation into steps whose temporaries take at most
``1/SCAN_TEMP_SHARE`` of it (``scan_step``).  Where the backend reports
no memory limit (XLA:CPU) launches keep the paper's 2^27 nnz and the scan
runs whole reservations.
"""
from __future__ import annotations

import functools
import math

LANE = 256

# the paper's per-block nnz bound (§4.2, a GPU memory constraint): the
# launch size where the device reports no memory limit
PAPER_MAX_NNZ_PER_BLOCK = 1 << 27
# one launch's index/value words take at most 1/LAUNCH_SHARE of device
# memory, so a streamed regime's queues stay a few percent of it
LAUNCH_SHARE = 256
# one scan step's temporaries take at most 1/SCAN_TEMP_SHARE of device memory
SCAN_TEMP_SHARE = 8


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (1 for n <= 1)."""
    return 1 << max(0, math.ceil(math.log2(max(1, n))))


def pad_pow2(n: int, floor: int = LANE) -> int:
    """Power-of-two bucket for ``n``, never below ``floor``."""
    return max(floor, next_pow2(n))


def pad_multiple(n: int, multiple: int = LANE) -> int:
    """Round ``n`` up to a multiple (minimum one multiple)."""
    return max(multiple, -(-n // multiple) * multiple)


def pad_bucket(n: int, multiple: int = LANE) -> int:
    """Size-class rounding: round ``n`` up to the next of 8 geometrically
    spaced classes per power-of-two octave (classes are LANE multiples).

    With ``n`` in (2^(k-1), 2^k] the class step is ``max(multiple,
    2^(k-3))``, so the overshoot is < 2^(k-3) < n/4 — at most 25% padded
    waste — while the number of distinct buckets below any bound N is at
    most ``8·log2(N)`` plus a constant.  That bound is what keeps the
    in-memory regime's jit cache (reservation is a traced shape) from
    growing linearly with launch size.
    """
    n = max(int(n), multiple)
    step = max(multiple, (1 << (n - 1).bit_length()) >> 3)
    return -(-n // step) * step


@functools.cache
def device_memory_bytes() -> int | None:
    """Memory limit of the default device, or None where the backend
    reports none (XLA:CPU)."""
    import jax
    stats = jax.devices()[0].memory_stats()
    return int(stats["bytes_limit"]) if stats and "bytes_limit" in stats \
        else None


def nnz_bytes(order: int, value_itemsize: int) -> int:
    """Device bytes of one nnz slot: hi + lo words, value, int32 bases."""
    return 4 + 4 + value_itemsize + 4 * order


def default_launch_nnz(order: int, value_itemsize: int,
                       device_bytes: int | None) -> int:
    """Default nnz per block and launch: the largest power of two whose
    slots take at most ``1/LAUNCH_SHARE`` of ``device_bytes`` (the paper's
    2^27 where the device reports no limit)."""
    if device_bytes is None:
        return PAPER_MAX_NNZ_PER_BLOCK
    fit = device_bytes // (LAUNCH_SHARE * nnz_bytes(order, value_itemsize))
    return max(LANE, 1 << (max(1, fit).bit_length() - 1))


def scan_temp_bytes_per_nnz(order: int, rank: int, itemsize: int) -> int:
    """Device temporaries of the XLA scan path per nnz slot of one step.

    Two ``(step, R)`` rows (the Hadamard partial and its segment sums), the
    step's ``(step, N)`` int32 bases slice, whose minor dimension TPU
    tiling pads to 128 lanes, and the int32 index, coordinate and segment
    words.  ``compiled.memory_analysis()`` for v5e at NELL-2 widths (order
    3, f32) gives 801-805 B at rank 32 and 1059 B at rank 64; this model
    gives 808 and 1064.
    """
    return 2 * rank * itemsize + 4 * (128 + 2 * order + 4)


def scan_step(reservation: int, *, order: int, rank: int, itemsize: int,
              device_bytes: int | None) -> int:
    """nnz slots per scan step: ``reservation`` halved (while even) until
    the step's temporaries fit ``1/SCAN_TEMP_SHARE`` of ``device_bytes``;
    the whole reservation where the device reports no limit."""
    step = int(reservation)
    if device_bytes is None:
        return step
    per = scan_temp_bytes_per_nnz(order, rank, itemsize)
    while step % 2 == 0 and step * per * SCAN_TEMP_SHARE > device_bytes:
        step //= 2
    return step


def scan_temp_bytes(reservation: int, *, order: int, rank: int,
                    itemsize: int, device_bytes: int | None) -> int:
    """Predicted device temporaries of the scan over one launch: one step's
    per-slot temporaries, plus a copy of the launch's index, value and
    bases words where steps cut it (XLA relays the launch out into steps;
    v5e at NELL-2 widths, rank 64: 1100 B per step slot, this model 1112)."""
    step = scan_step(reservation, order=order, rank=rank, itemsize=itemsize,
                     device_bytes=device_bytes)
    relayout = reservation * 4 * (order + 3) if step < reservation else 0
    return step * scan_temp_bytes_per_nnz(order, rank, itemsize) + relayout
