"""Compiles of the main path for a TPU v5e that is described, not attached.

The TPU compiler is installed with jax, so these compile the real programs
at NELL-2 widths (FROSTT: 12,092 x 9,184 x 28,818, 76,879,419 nnz, rank 32)
from ``ShapeDtypeStruct``s alone: nothing runs and no array is allocated.
They catch what interpret mode cannot — a program that does not fit the
chip's memory, or a kernel Mosaic refuses — at no chip time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import linearize as lin
from repro.core.distributed import shard_nnz, sharded_mttkrp_fn
from repro.core.launches import default_reservation, stacked_mttkrp
from repro.core.padding import default_launch_nnz, scan_step, scan_temp_bytes

NELL2_DIMS = (12092, 9184, 28818)
NELL2_NNZ = 76_879_419
RANK = 32
# memory_stats()["bytes_limit"] of one v5e chip, as measured on the chip
V5E_BYTES_LIMIT = 16_909_336_064


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    cache_was_on = jax.config.jax_enable_compilation_cache
    # a compile for a described chip cannot be read back without the chip:
    # keep it out of any persistent cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # noqa: BLE001 — any failure means skip
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    jax.config.update("jax_enable_compilation_cache", cache_was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _fields():
    re = lin.reencode_spec(lin.LinearSpec.make(NELL2_DIMS), 64)
    return tuple(re.field_bits), tuple(re.field_shift)


def _factors(sharding, rank=RANK):
    return tuple(jax.ShapeDtypeStruct((d, rank), jnp.float32,
                                      sharding=sharding) for d in NELL2_DIMS)


@pytest.mark.parametrize("stacked,rank", [(True, 32), (False, 64)])
def test_scan_mttkrp_fits_one_chip_at_nell2_widths(one_chip, stacked, rank):
    """The XLA scan MTTKRP of the in-memory regime (all launches stacked)
    and of the streamed regime (one launch per call) compiles for one v5e
    at NELL-2 widths with the default launch size, fits the chip, and its
    temporaries stay within the byte predictor's model."""
    launch = default_launch_nnz(3, 4, V5E_BYTES_LIMIT)
    res = default_reservation(launch)
    lead = (-(-NELL2_NNZ // launch),) if stacked else ()
    step = scan_step(res, order=3, rank=rank, itemsize=4,
                     device_bytes=V5E_BYTES_LIMIT)
    assert step < res if rank == 64 else step == res

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(lead + shape, dtype, sharding=one_chip)

    fields, shifts = _fields()
    mode = 2                                   # the longest output mode
    compiled = stacked_mttkrp.lower(
        s((res,), jnp.uint32), s((res,), jnp.uint32), s((res,), jnp.float32),
        s((res, 3), jnp.int32), _factors(one_chip, rank),
        re_fields=fields, re_shifts=shifts, mode=mode,
        out_rows=NELL2_DIMS[mode], resolution="register", copies=8,
        step=step).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.temp_size_in_bytes
             + m.output_size_in_bytes)
    assert total < V5E_BYTES_LIMIT, total
    assert m.temp_size_in_bytes <= scan_temp_bytes(
        res, order=3, rank=rank, itemsize=4, device_bytes=V5E_BYTES_LIMIT)


@pytest.mark.parametrize("variant,out_rows", [("segment", NELL2_DIMS[0]),
                                              ("stash", 512)])
def test_fused_kernel_refused_by_mosaic(one_chip, variant, out_rows):
    """Mosaic refuses the fused kernel's in-kernel factor-row gather, which
    is why ``kernel='pallas'`` raises on a TPU.  When this compiles, the
    refusal in ``repro.kernels.fused.require_fused_support`` can go."""
    from repro.kernels.fused import _fused_flat
    t = 1 << 20
    dims = (out_rows,) + NELL2_DIMS[1:]

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fields, shifts = _fields()
    factors = tuple(s((d, RANK), jnp.float32) for d in dims)
    with pytest.raises(ValueError, match="Shape mismatch in input, indices"):
        _fused_flat.lower(
            s((t,), jnp.uint32), s((t,), jnp.uint32), s((t,), jnp.float32),
            s((t, 3), jnp.int32), factors, field_bits=fields,
            field_shifts=shifts, mode=0, out_rows=out_rows, variant=variant,
            tile=256, interpret=False).compile()


def test_sharded_mttkrp_compiles_for_four_chips(topo):
    """The sharded regime's MTTKRP over a (4, 1) data x model mesh of v5e
    chips: each chip holds a quarter of the nnz, fits its memory, and the
    outputs merge with an all-reduce."""
    mesh = Mesh(np.array(topo.devices[:4]).reshape(4, 1), ("data", "model"))
    per = shard_nnz(NELL2_NNZ, 4)
    step = scan_step(per, order=3, rank=RANK, itemsize=4,
                     device_bytes=V5E_BYTES_LIMIT)
    nnz_sh = NamedSharding(mesh, P("data"))
    bases_sh = NamedSharding(mesh, P("data", None))
    fields, shifts = _fields()
    mode = 0
    fn = sharded_mttkrp_fn(mesh, re_fields=fields, re_shifts=shifts,
                           out_rows=NELL2_DIMS[mode], mode=mode, step=step)
    compiled = fn.lower(
        jax.ShapeDtypeStruct((4, per), jnp.uint32, sharding=nnz_sh),
        jax.ShapeDtypeStruct((4, per), jnp.uint32, sharding=nnz_sh),
        jax.ShapeDtypeStruct((4, per), jnp.float32, sharding=nnz_sh),
        jax.ShapeDtypeStruct((4, per, 3), jnp.int32, sharding=bases_sh),
        _factors(NamedSharding(mesh, P(None, "model")))).compile()
    assert "all-reduce" in compiled.as_text()
    m = compiled.memory_analysis()
    per_chip = (m.argument_size_in_bytes + m.temp_size_in_bytes
                + m.output_size_in_bytes)
    assert per_chip < V5E_BYTES_LIMIT, per_chip
    # a quarter of the nnz arrays per chip, not the whole tensor
    assert m.argument_size_in_bytes < per * 24 + (1 << 28)
