"""Device-resident launch cache: padded launches prepared once, stacked.

The paper's BLCO pitch is that blocking "reduces kernel launching overhead";
PR 2's engine still paid one XLA dispatch and one host numpy padding pass
per launch per ``mttkrp`` call.  This module is the fix:

* :class:`LaunchCache` pads every launch to ONE reservation shape (reusing
  ``prepare_chunks``/``ReservationSpec`` from the streaming layer, so both
  regimes share the padding code and the byte accounting), stacks the
  chunks into ``(L, reservation)`` device arrays, and uploads them once;
* :func:`stacked_mttkrp` replaces the per-launch Python loop + ``out = out
  + ...`` chain with a single jitted ``lax.scan`` over the stacked
  launches — ONE dispatch per MTTKRP call regardless of launch count, and
  per-step intermediates (coordinates, gathered factor rows) bounded by the
  scan step (:func:`scan_step_for`: the reservation, cut further where its
  temporaries would crowd device memory) instead of the full nnz count.

The stacked arrays are also the zero-copy source for the fused Pallas
pipeline (``repro.kernels.fused``): ``flat()`` reshapes ``(L, reservation)``
to one contiguous ``(L * reservation,)`` stream on device, which the fused
kernel tiles directly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace as obs_trace

from .blco import BLCOTensor
from .counters import record_dispatch, record_resolution
from .mttkrp import DEFAULT_COPIES, auto_resolution, launch_mttkrp_impl
from . import padding
from .padding import (device_memory_bytes, nnz_bytes, pad_bucket, pad_multiple,
                      scan_step)


def default_reservation(max_launch: int) -> int:
    """The in-memory regime's default reservation for a given largest launch.

    Size-class rounding (``pad_bucket``): bounded distinct reservations
    (each reservation is a traced shape, i.e. a jit cache key for the
    stacked scan and the fused kernel), ≤ 25% padding waste.  The single
    definition the cache builder, the byte predictor and the trace-tier
    cache-churn audit all share — so the audited rounding IS the shipped
    rounding.
    """
    return pad_bucket(max_launch)


def scan_step_for(reservation: int, order: int, rank: int,
                  itemsize: int) -> int:
    """The scan step of a ``reservation``-slot launch at rank ``rank`` on the
    default device."""
    return scan_step(reservation, order=order, rank=rank, itemsize=itemsize,
                     device_bytes=device_memory_bytes())


def scan_temp_bytes(reservation: int, order: int, rank: int,
                    itemsize: int) -> int:
    """Predicted device temporaries of the scan on the default device (the
    working set it adds on top of the resident tensor and factors)."""
    return padding.scan_temp_bytes(reservation, order=order, rank=rank,
                                   itemsize=itemsize,
                                   device_bytes=device_memory_bytes())


@functools.partial(
    jax.jit,
    static_argnames=("re_fields", "re_shifts", "mode", "out_rows",
                     "resolution", "copies", "step"))
def stacked_mttkrp(hi, lo, vals, bases, factors, *,
                   re_fields: tuple, re_shifts: tuple, mode: int,
                   out_rows: int, resolution: str, copies: int, step: int):
    """Single-dispatch MTTKRP over a stream of launches.

    Its compiled module is ``jit_stacked_mttkrp``, after this function's
    name: the benchmark's trace readers find the MTTKRP's device time by
    that name, so renaming the function silences them.

    hi/lo: (..., S) uint32; vals: (..., S); bases: (..., S, N) int32 — one
    launch (the streamed regime) or a stack of L launches (the launch
    cache); factors: tuple of (I_n, R) arrays.  The stream is cut into
    consecutive steps of ``step`` slots (``step`` divides S) and
    ``lax.scan`` runs the per-launch dataflow on each step in order,
    accumulating into one (out_rows, rank) output.  Steps never need launch
    boundaries: every slot carries its block's coordinate bases.  With
    ``step == S`` the accumulation order is the per-launch loop's.
    """
    hi = hi.reshape(-1, step)
    lo = lo.reshape(-1, step)
    vals = vals.reshape(-1, step)
    bases = bases.reshape(-1, step, bases.shape[-1])
    factors = tuple(factors)
    rank = factors[0].shape[1]
    # accumulate at the promoted precision: float64 tensor values against
    # float32 factors must not be silently downcast by the accumulator
    out0 = jnp.zeros((out_rows, rank), jnp.result_type(vals, factors[0]))

    def body(out, xs):
        h, l, v, b = xs
        return out + launch_mttkrp_impl(
            h, l, v, b, factors, re_fields=re_fields, re_shifts=re_shifts,
            mode=mode, out_rows=out_rows, resolution=resolution,
            copies=copies), None

    out, _ = jax.lax.scan(body, out0, (hi, lo, vals, bases))
    return out


class LaunchCache:
    """Stacked, device-resident, reservation-padded launches of one tensor.

    Built once per plan; every ``mttkrp`` call afterwards is one jitted
    dispatch with zero host-side work.  The reservation defaults to the
    largest launch rounded up to a geometric size class
    (``default_reservation``): near-memory-tight (≤ 25% padding) while
    keeping the number of distinct traced shapes — and therefore compiled
    executables — logarithmic in launch size, unlike a bare ``LANE``
    multiple (the streaming regime uses coarser power-of-two cross-tensor
    buckets instead).

    Padding waste is bounded by construction: ``build_blco`` splits every
    block to ``max_nnz_per_block`` and greedily batches blocks into
    launches up to the same budget, so all launches except the final tail
    are at least ``budget - max_block`` nnz — stacking to the max-launch
    reservation is within a small constant of the tight footprint (there is
    no "one huge launch + many tiny ones" shape to blow it up).
    """

    def __init__(self, hi, lo, vals, bases, *, re_fields: tuple,
                 re_shifts: tuple, dims: tuple):
        self.hi = hi                    # (L, R) uint32
        self.lo = lo                    # (L, R) uint32
        self.vals = vals                # (L, R) float
        self.bases = bases              # (L, R, N) int32
        self.re_fields = tuple(re_fields)
        self.re_shifts = tuple(re_shifts)
        self.dims = tuple(dims)
        self.closed = False

    # ------------------------------------------------------------ construct
    @classmethod
    def from_blco(cls, blco: BLCOTensor,
                  reservation_nnz: int | None = None) -> "LaunchCache":
        """Pad + stack + upload every launch of ``blco`` (host work, once)."""
        from repro.faults import inject as faults
        from .streaming import prepare_chunks
        # the device-resident regime's single allocation moment: a real
        # RESOURCE_EXHAUSTED surfaces from the device_put below exactly
        # like this injected probe, and the plan_for/ServiceEngine ladder
        # demotes either to a streamed regime
        faults.maybe_fail("plan.alloc")
        max_launch = max((l.nnz for l in blco.launches), default=1)
        if reservation_nnz:
            if int(reservation_nnz) < max_launch:
                raise ValueError(
                    f"reservation {int(reservation_nnz)} smaller than "
                    f"largest launch ({max_launch} nnz)")
            # the byte predictor (launch_cache_bytes) and the fused Pallas
            # tiler both assume LANE-multiple reservations; a ragged explicit
            # reservation is rounded up, never honoured as-is
            res = pad_multiple(int(reservation_nnz))
        else:
            res = default_reservation(max_launch)
        # pad, stack and put; the puts are issued, not awaited
        with obs_trace.span("plan.upload", "plan",
                            launches=len(blco.launches), reservation=res):
            chunks = prepare_chunks(blco, res)
            return cls.from_chunks(chunks, blco, reservation_nnz=res)

    @classmethod
    def from_chunks(cls, chunks, blco: BLCOTensor, *,
                    reservation_nnz: int) -> "LaunchCache":
        """Stack already reservation-padded chunks (e.g. a service handle's)."""
        n_launch = len(chunks)
        res = int(reservation_nnz)
        order = blco.order
        if n_launch:
            hi = np.stack([c[0] for c in chunks])
            lo = np.stack([c[1] for c in chunks])
            vals = np.stack([c[2] for c in chunks])
            bases = np.stack([c[3] for c in chunks])
        else:
            hi = np.zeros((0, res), np.uint32)
            lo = np.zeros((0, res), np.uint32)
            vals = np.zeros((0, res), blco.values.dtype)
            bases = np.zeros((0, res, order), np.int32)
        return cls(jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(vals),
                   jnp.asarray(bases), re_fields=blco.re.field_bits,
                   re_shifts=blco.re.field_shift, dims=blco.dims)

    # ------------------------------------------------------------ introspect
    @property
    def num_launches(self) -> int:
        return int(self.hi.shape[0])

    @property
    def reservation(self) -> int:
        return int(self.hi.shape[1])

    @property
    def order(self) -> int:
        return len(self.dims)

    def device_bytes(self) -> int:
        """Exact resident footprint: hi + lo + vals + bases (stacked)."""
        if self.closed:
            return 0
        return int(self.hi.nbytes + self.lo.nbytes + self.vals.nbytes
                   + self.bases.nbytes)

    def flat(self):
        """Device-side flat views: (T,) hi/lo/vals + (T, N) bases with
        ``T = L * reservation`` — the fused Pallas pipeline's input stream."""
        t = self.num_launches * self.reservation
        return (self.hi.reshape(t), self.lo.reshape(t), self.vals.reshape(t),
                self.bases.reshape(t, self.order))

    # --------------------------------------------------------------- compute
    def mttkrp(self, factors, mode: int, *, resolution: str = "auto",
               copies: int = DEFAULT_COPIES):
        """Single-dispatch MTTKRP (XLA scan path) from the cached launches."""
        if self.closed:
            raise RuntimeError("launch cache is closed")
        assert 0 <= mode < self.order
        factors = tuple(jnp.asarray(f) for f in factors)
        out_dtype = jnp.result_type(self.vals, factors[0])
        if self.num_launches == 0:
            rank = factors[0].shape[1]
            return jnp.zeros((self.dims[mode], rank), out_dtype)
        if resolution == "auto":
            resolution = auto_resolution(self.dims[mode], out_dtype)
        step = scan_step_for(self.reservation, self.order,
                             factors[0].shape[1], out_dtype.itemsize)
        record_dispatch()
        record_resolution(resolution)
        # span covers the host-side issue of the one scan dispatch (async);
        # the wait for the device is the plan's plan.fence span
        with obs_trace.span("launch_cache.scan", "dispatch",
                            launches=self.num_launches, mode=mode,
                            resolution=resolution):
            return stacked_mttkrp(
                self.hi, self.lo, self.vals, self.bases, factors,
                re_fields=self.re_fields, re_shifts=self.re_shifts, mode=mode,
                out_rows=self.dims[mode], resolution=resolution, copies=copies,
                step=step)

    # ---------------------------------------------------------------- release
    def delete(self) -> None:
        """Release the device buffers (the cache must not be used after)."""
        self.closed = True
        for arr in (self.hi, self.lo, self.vals, self.bases):
            try:
                arr.delete()
            except Exception:   # already deleted / backend without delete()
                pass


def launch_cache_bytes(blco: BLCOTensor) -> int:
    """Predicted device footprint of a ``LaunchCache`` for ``blco``:
    L stacked launches x (hi + lo + vals + bases) at the default
    size-class reservation — what ``DeviceBLCO``/``InMemoryPlan`` hold."""
    if not blco.launches:
        return 0
    max_launch = max(l.nnz for l in blco.launches)
    res = default_reservation(max_launch)
    return len(blco.launches) * res * nnz_bytes(blco.order,
                                                blco.values.dtype.itemsize)
