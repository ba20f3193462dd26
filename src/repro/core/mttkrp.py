"""Mode-agnostic BLCO MTTKRP with opportunistic conflict resolution (paper §5).

One implementation serves *every* mode — the paper's headline property. Per
launch the dataflow is the paper's two phases:

  processing phase: coalesced load of (hi, lo) stored indices -> shift+mask
      de-linearization of every mode (§5.1.1);
  computing phase:  gather non-target factor rows -> hadamard x value ->
      on-the-fly segment discovery on the target-index stream -> segmented
      reduction -> one update per *segment* (not per nnz) into the output
      (§5.1.2), either directly ("register" resolution, §5.2) or via C partial
      copies merged at the end ("hierarchical" resolution, §5.1 steps 5-7).

The XLA path below is the faithful reference dataflow; `repro.kernels`
provides the fused single-``pallas_call`` version of the whole pipeline.
Both are validated against the dense matricization oracle in tests.

Execution is launch-cache driven: ``mttkrp`` pads the launches ONCE into a
device-resident :class:`repro.core.launches.LaunchCache` and then every call
is a single jitted dispatch (``lax.scan`` over the stacked launches) with
zero host-side work.  ``mttkrp_per_launch`` keeps the old per-launch
loop — one dispatch and one numpy padding pass per launch per call — as the
benchmark baseline the fused path is measured against.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import u64
from .blco import BLCOTensor
from .counters import record_dispatch
from .padding import pad_pow2

# TPU analogue of the paper's "#SMs" constant in the §5.3 heuristic: below this
# target-mode length, update contention dominates and the hierarchical
# (multi-copy) mechanism wins; above it, direct per-segment updates win.
CONTENTION_THRESHOLD = 128
DEFAULT_COPIES = 8

KERNELS = ("xla", "pallas")


def validate_kernel(kernel: str) -> str:
    """Reject unknown compute-kernel names, and the fused Pallas kernel
    where it cannot compile (one validator for every layer)."""
    if kernel not in KERNELS:
        raise ValueError(f"unknown kernel {kernel!r}; expected one of "
                         f"{KERNELS}")
    if kernel == "pallas":
        from repro.kernels.fused import require_fused_support
        require_fused_support()
    return kernel


def choose_resolution(mode_len: int, threshold: int = CONTENTION_THRESHOLD) -> str:
    """Paper §5.3 adaptation heuristic, re-keyed for TPU (DESIGN.md §2)."""
    return "hierarchical" if mode_len < threshold else "register"


def delinearize(re_fields, re_shifts, idx_hi, idx_lo):
    """Recover all mode coordinates from stored (hi, lo) uint32 index words.

    re_fields/re_shifts: static tuples. Returns list of int32 arrays (no block
    base applied).
    """
    coords = []
    for shift, width in zip(re_shifts, re_fields):
        coords.append(u64.extract_field(idx_hi, idx_lo, shift, width).astype(jnp.int32))
    return coords


def _segment_compress(tgt, partial):
    """On-the-fly segment discovery + segmented reduction (paper §5.1 steps 3-5).

    tgt: (T,) int32 target-mode indices in ALTO order (NOT sorted by target —
    segments are runs of equal target, discovered on the fly, exactly the
    paper's opportunistic scheme). Returns (seg_tgt, seg_sums) of length T where
    only the first #segments rows are meaningful; the rest are (0, 0-rows).
    """
    n = tgt.shape[0]
    flags = jnp.concatenate([jnp.ones((1,), jnp.int32),
                             (tgt[1:] != tgt[:-1]).astype(jnp.int32)])
    seg_id = jnp.cumsum(flags) - 1                       # (T,) 0-based segment ids
    seg_sums = jax.ops.segment_sum(partial, seg_id, num_segments=n)
    seg_tgt = jnp.zeros((n,), jnp.int32).at[seg_id].max(tgt)
    return seg_tgt, seg_sums


def launch_mttkrp_impl(idx_hi, idx_lo, vals, bases, factors, *,
                       re_fields: tuple, re_shifts: tuple, mode: int,
                       out_rows: int, resolution: str, copies: int):
    """One launch's MTTKRP dataflow (traceable; reused under ``lax.scan``).

    idx_hi/idx_lo: (T,) uint32 stored indices. vals: (T,). bases: (T, N) int32
    per-element block coordinate bases (upper bits << field width). factors:
    tuple of (I_n, R) arrays. Returns (out_rows, R) partial output.
    """
    coords = delinearize(re_fields, re_shifts, idx_hi, idx_lo)
    coords = [c + bases[:, n] for n, c in enumerate(coords)]

    # promote, never downcast: float64 values against float32 factors
    # accumulate in float64 (jnp.result_type), on every kernel path
    partial = vals[:, None].astype(jnp.result_type(vals, factors[0]))
    for m, f in enumerate(factors):
        if m == mode:
            continue
        partial = partial * jnp.take(f, coords[m], axis=0)
    tgt = coords[mode]

    if resolution == "direct":
        # per-nnz scatter (no conflict resolution) — the COO dataflow on the
        # BLCO layout; cheapest on hardware with fast serialized scatter
        # (CPU); the paper's mechanisms below win where conflicting updates
        # serialize (GPU atomics / TPU scatter with duplicate rows).
        out = jnp.zeros((out_rows, partial.shape[1]), partial.dtype)
        return out.at[tgt].add(partial)

    seg_tgt, seg_sums = _segment_compress(tgt, partial)

    if resolution == "register":
        out = jnp.zeros((out_rows, partial.shape[1]), partial.dtype)
        return out.at[seg_tgt].add(seg_sums)
    elif resolution == "hierarchical":
        # Spread segments over C partial copies (paper's factor-matrix copies,
        # step 6) and merge (step 7). Reduces duplicate-row scatter contention.
        n = seg_tgt.shape[0]
        copy_id = (jnp.arange(n, dtype=jnp.int32) % copies)
        out = jnp.zeros((copies, out_rows, partial.shape[1]), partial.dtype)
        out = out.at[copy_id, seg_tgt].add(seg_sums)
        return out.sum(axis=0)
    raise ValueError(f"unknown resolution {resolution!r}")


launch_mttkrp = functools.partial(
    jax.jit,
    static_argnames=("re_fields", "re_shifts", "mode", "out_rows",
                     "resolution", "copies"))(launch_mttkrp_impl)


def launch_cache_for(blco: BLCOTensor):
    """The tensor's attached device-resident launch cache (built once).

    The cache holds device memory for the tensor's lifetime and is NOT
    visible to engine/service admission accounting — it backs the
    free-function ``mttkrp`` convenience API only.  Engine plans build and
    own their own cache (via ``DeviceBLCO``) so that ``plan.close()`` can
    release it without invalidating other users.  Call
    :func:`clear_launch_cache` to drop the attached copy.
    """
    from .launches import LaunchCache
    cache = getattr(blco, "_launch_cache", None)
    if cache is None or cache.closed:
        cache = LaunchCache.from_blco(blco)
        blco._launch_cache = cache
    return cache


def clear_launch_cache(blco: BLCOTensor) -> int:
    """Release the launch cache attached by ``mttkrp``/``launch_cache_for``.

    Returns the device bytes freed (0 when no cache was attached).
    """
    cache = getattr(blco, "_launch_cache", None)
    if cache is None:
        return 0
    freed = cache.device_bytes()
    cache.delete()
    blco._launch_cache = None
    return freed


def mttkrp(blco: BLCOTensor, factors, mode: int, *,
           resolution: str = "auto", copies: int = DEFAULT_COPIES,
           pad: bool = True, cache=None):
    """Full mode-n MTTKRP over all launches of a BLCO tensor.

    factors: list/tuple of N device arrays (I_n, R). Returns (I_mode, R).

    The padded launches are prepared ONCE (a device-resident ``LaunchCache``
    attached to ``blco``, or pass ``cache=`` explicitly) and the whole call
    is a single jitted ``lax.scan`` dispatch — zero per-call host work.
    ``pad=False`` keeps the exact-shape per-launch reference path (one
    dispatch per launch, no padding slots) used by the padding-exactness
    property tests.
    """
    assert 0 <= mode < blco.order
    if not pad:
        return mttkrp_per_launch(blco, factors, mode, resolution=resolution,
                                 copies=copies, pad=False)
    cache = cache if cache is not None else launch_cache_for(blco)
    return cache.mttkrp(factors, mode, resolution=resolution, copies=copies)


def mttkrp_per_launch(blco: BLCOTensor, factors, mode: int, *,
                      resolution: str = "auto", copies: int = DEFAULT_COPIES,
                      pad: bool = True):
    """The pre-launch-cache reference path: one host padding pass + one
    jitted dispatch PER LAUNCH per call.

    Kept as (a) the exact-shape ``pad=False`` oracle for the padding
    property tests and (b) the benchmark baseline that ``BENCH_3.json``
    measures the single-dispatch paths against.
    """
    assert 0 <= mode < blco.order
    if resolution == "auto":
        resolution = choose_resolution(blco.dims[mode])
    factors = tuple(jnp.asarray(f) for f in factors)
    rank = factors[0].shape[1]
    out = jnp.zeros((blco.dims[mode], rank),
                    jnp.result_type(jnp.asarray(blco.values[:0]), factors[0]))

    bases_all = blco.block_upper_bases()           # (num_blocks, N)
    block_ids = blco.element_block_ids()           # (nnz,)
    for launch in blco.launches:
        s, e = launch.start, launch.end
        n = e - s
        padded = pad_pow2(n) if pad else n
        hi = np.zeros(padded, np.uint32)
        lo = np.zeros(padded, np.uint32)
        vals = np.zeros(padded, blco.values.dtype)
        bases = np.zeros((padded, blco.order), np.int32)
        hi[:n] = blco.idx_hi[s:e]
        lo[:n] = blco.idx_lo[s:e]
        vals[:n] = blco.values[s:e]
        bases[:n] = bases_all[block_ids[s:e]]
        record_dispatch()
        out = out + launch_mttkrp(
            jnp.asarray(hi), jnp.asarray(lo), jnp.asarray(vals),
            jnp.asarray(bases), factors,
            re_fields=blco.re.field_bits, re_shifts=blco.re.field_shift,
            mode=mode, out_rows=blco.dims[mode],
            resolution=resolution, copies=copies)
    return out


class DeviceBLCO:
    """Device-resident BLCO tensor for in-memory benchmarking/serving.

    The paper's in-memory regime: the padded launches are uploaded once (a
    stacked :class:`~repro.core.launches.LaunchCache`) and every ``mttkrp``
    call is a single jitted dispatch with zero host work — a ``lax.scan``
    over the stacked launches on the XLA path, or one fused ``pallas_call``
    pipeline on the Pallas path (``kernel="pallas"``).
    """

    def __init__(self, blco: BLCOTensor, *, kernel: str = "xla",
                 reservation_nnz: int | None = None):
        from .launches import LaunchCache
        validate_kernel(kernel)
        self.cache = LaunchCache.from_blco(blco,
                                           reservation_nnz=reservation_nnz)
        self.dims = blco.dims
        self.order = blco.order
        self.kernel = kernel

    def device_bytes(self) -> int:
        """Exact device footprint: hi + lo + vals + bases (stacked, padded)."""
        return self.cache.device_bytes()

    def mttkrp(self, factors, mode: int, *, resolution: str = "auto",
               copies: int = DEFAULT_COPIES, kernel: str | None = None):
        kernel = kernel if kernel is not None else self.kernel
        if kernel == "pallas":
            from repro.kernels.fused import fused_cache_mttkrp
            return fused_cache_mttkrp(self.cache, factors, mode,
                                      resolution=resolution)
        return self.cache.mttkrp(factors, mode, resolution=resolution,
                                 copies=copies)

    def delete(self) -> None:
        """Release the device buffers (the arrays must not be used after)."""
        self.cache.delete()


# --------------------------------------------------------------------- oracle
def khatri_rao(mats) -> np.ndarray:
    """Column-wise Kronecker product of a list of (I_n, R) matrices."""
    out = mats[0]
    for m in mats[1:]:
        r = out.shape[1]
        out = (out[:, None, :] * m[None, :, :]).reshape(-1, r)
    return out


def mttkrp_dense_oracle(t, factors, mode: int) -> np.ndarray:
    """Dense-matricization oracle: X_(n) @ KR(...) over the non-target modes.

    The element-wise MTTKRP result is convention-independent; what matters is
    that the matricization's column ordering matches the Khatri-Rao row
    ordering. `SparseTensor.matricize` uses a C-order reshape (highest
    remaining mode varies fastest), so the KR list must be ascending (lowest
    mode listed first = slowest-varying).
    """
    xs = t.matricize(mode).astype(np.float64)
    others = [np.asarray(factors[m], np.float64)
              for m in range(len(factors)) if m != mode]
    kr = khatri_rao(others)
    return xs @ kr
