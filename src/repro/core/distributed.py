"""Distributed sparse MTTKRP / CP-ALS over a device mesh (beyond-paper).

The paper targets a single GPU (+ host streaming). Scaling its format out to a
pod is natural because BLCO is list-based and mode-agnostic:

* **nnz parallelism (data axis)** — the sorted nnz stream is range-partitioned
  across devices (each shard holds whole launches); every device runs the same
  mode-agnostic launch kernel on its shard and the per-mode outputs are merged
  with one ``psum`` (or ``psum_scatter`` when the factor is row-sharded).
  Because partials are segment-compressed *before* the collective, the reduce
  payload per device is O(I_mode x R), independent of nnz.
* **rank parallelism (model axis)** — MTTKRP columns are independent, so the
  factor matrices shard along R with *zero* communication in MTTKRP itself;
  CP-ALS then needs only an R x R gram psum per mode (tiny).

This mirrors the DP x TP mesh used by the LM half of the framework and is
exercised on 8 fake XLA devices in tests, compiled for a v5e 2x2 mesh in
``tests/test_tpu_compile.py``, and run on four chips by
``chip_smoke.py --chips 4``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .blco import BLCOTensor
from .counters import record_resolution
from .launches import scan_step_for, stacked_mttkrp
from .mttkrp import DEFAULT_COPIES, auto_resolution
from .padding import nnz_bytes, pad_bucket


def shard_nnz(nnz: int, num_shards: int) -> int:
    """Padded nnz slots per shard: an equal range split, rounded up to a
    size class so the scan step can cut it evenly."""
    return pad_bucket(-(-nnz // num_shards))


def shard_launch_arrays(blco: BLCOTensor, num_shards: int):
    """Range-partition the nnz stream into equal padded shards (host side).

    Returns dict of (num_shards, per) arrays ready for device_put with a
    sharded layout. Each shard is independent: the segment discovery never
    crosses shard boundaries (a split segment just produces one extra merged
    update, exactly like the paper's tile-boundary handling).  Pad slots
    carry value 0 and coordinate 0, so they add exact zeros.
    """
    n = blco.nnz
    per = shard_nnz(n, num_shards)
    padded = per * num_shards
    hi = np.zeros(padded, np.uint32); hi[:n] = blco.idx_hi
    lo = np.zeros(padded, np.uint32); lo[:n] = blco.idx_lo
    vals = np.zeros(padded, blco.values.dtype); vals[:n] = blco.values
    bases = np.zeros((padded, blco.order), np.int32)
    bases[:n] = blco.block_upper_bases()[blco.element_block_ids()]
    return {
        "idx_hi": hi.reshape(num_shards, per),
        "idx_lo": lo.reshape(num_shards, per),
        "vals": vals.reshape(num_shards, per),
        "bases": bases.reshape(num_shards, per, blco.order),
    }


def shard_bytes(blco: BLCOTensor, num_shards: int) -> int:
    """Device bytes of one shard's padded nnz arrays."""
    return shard_nnz(blco.nnz, num_shards) * nnz_bytes(
        blco.order, blco.values.dtype.itemsize)


def sharded_mttkrp_fn(mesh, *, re_fields: tuple, re_shifts: tuple,
                      out_rows: int, mode: int, step: int,
                      data_axis="data", model_axis="model"):
    """The jitted mode-``mode`` MTTKRP over ``mesh``, from shapes alone.

    Arguments: (hi, lo, vals) of shape (shards, per) and bases of shape
    (shards, per, N), sharded over ``data_axis`` on the leading dim, and a
    tuple of (I_n, R) factors, sharded over ``model_axis`` along R.  Each
    device scans its shard in steps of ``step`` slots (the single-device
    dataflow, ``stacked_mttkrp``) and the per-device outputs merge with one
    ``psum`` over the data axis.  The reduction is ``auto_resolution``'s,
    from the output's dtype when the function is traced.
    """
    n_modes = len(re_fields)
    nnz_spec = P(data_axis)
    bases_spec = P(data_axis, None)
    factor_spec = P(None, model_axis)

    def _shard_fn(hi, lo, vals, bases, factors):
        resolution = auto_resolution(out_rows,
                                     jnp.result_type(vals, factors[0]))
        out = stacked_mttkrp(hi, lo, vals, bases, factors,
                             re_fields=re_fields, re_shifts=re_shifts,
                             mode=mode, out_rows=out_rows,
                             resolution=resolution, copies=DEFAULT_COPIES,
                             step=step)
        # one collective per mode; payload O(I_mode x R_shard), nnz-independent
        return jax.lax.psum(out, data_axis)

    return jax.jit(jax.shard_map(
        _shard_fn, mesh=mesh,
        in_specs=(nnz_spec, nnz_spec, nnz_spec, bases_spec,
                  tuple(factor_spec for _ in range(n_modes))),
        out_specs=factor_spec, check_vma=False))


def make_distributed_mttkrp(blco: BLCOTensor, mesh, *, data_axis="data",
                            model_axis="model"):
    """Build a jitted distributed mode-n MTTKRP over ``mesh``.

    Factors: replicated over data axis, sharded over model axis along R.
    nnz arrays: sharded over data axis (leading dim), replicated over model.
    """
    data_size = 1
    for ax in (data_axis if isinstance(data_axis, tuple) else (data_axis,)):
        data_size *= mesh.shape[ax]

    shards = shard_launch_arrays(blco, data_size)
    per = shards["vals"].shape[1]

    device_shards = {
        k: jax.device_put(v, jax.NamedSharding(
            mesh, P(data_axis, None) if k == "bases" else P(data_axis)))
        for k, v in shards.items()
    }

    @functools.lru_cache(maxsize=None)
    def _build(mode: int, rank: int, itemsize: int):
        step = scan_step_for(per, blco.order, rank, itemsize)
        return sharded_mttkrp_fn(
            mesh, re_fields=blco.re.field_bits, re_shifts=blco.re.field_shift,
            out_rows=blco.dims[mode], mode=mode, step=step,
            data_axis=data_axis, model_axis=model_axis)

    def run(factors, mode: int):
        factors = tuple(factors)
        out_dtype = np.result_type(blco.values.dtype, factors[0].dtype)
        record_resolution(auto_resolution(blco.dims[mode], out_dtype))
        return _build(mode, factors[0].shape[1], out_dtype.itemsize)(
            device_shards["idx_hi"], device_shards["idx_lo"],
            device_shards["vals"], device_shards["bases"], factors)

    run.device_shards = device_shards
    return run
