"""Registered hot paths: the jitted entry points the trace tier audits.

Each :class:`HotPath` names one jit-compiled dataflow the paper's pipeline
actually executes — the stacked-scan MTTKRP, the fused Pallas kernel (both
conflict-resolution variants), the streamed regime's per-launch body, and
the CP-ALS sweep update — together with a builder that traces it over
*abstract* inputs (``jax.ShapeDtypeStruct``), so auditing needs no device
and allocates no arrays.  Shapes are small representative instances; the
properties checked (no host callbacks, no narrowing on accumulation edges,
declared scatter uniqueness) are shape-independent because every primitive
the walk inspects appears identically at any size.

``path``/``symbol`` place findings in the lint framework's stable keying
(``pass:path:symbol``), so trace findings share the AST tier's baseline
and inline-suppression machinery unchanged.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .jaxprs import ClosedJaxpr, trace_jaxpr

# one small representative tensor: dims (8, 6, 4), re-encoded as 3+3+2-bit
# contiguous fields — the layout build_blco(dims=(8,6,4)) itself produces
_DIMS = (8, 6, 4)
_FIELDS = (3, 3, 2)
_SHIFTS = (0, 3, 6)
_RANK = 16
_RES = 256          # one LANE-multiple reservation


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _u32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.uint32)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _factors():
    return tuple(_f32(d, _RANK) for d in _DIMS)


@dataclasses.dataclass(frozen=True)
class HotPath:
    """One auditable jitted dataflow: identity + an abstract tracer."""
    name: str            # finding symbol (function the jaxpr came from)
    path: str            # repo-relative source path the finding points at
    build: object        # () -> ClosedJaxpr

    def trace(self) -> ClosedJaxpr:
        return self.build()


def _stacked(resolution: str):
    from repro.core.launches import stacked_mttkrp
    launches = 2
    return trace_jaxpr(
        stacked_mttkrp,
        _u32(launches, _RES), _u32(launches, _RES), _f32(launches, _RES),
        _i32(launches, _RES, len(_DIMS)), _factors(),
        re_fields=_FIELDS, re_shifts=_SHIFTS, mode=0, out_rows=_DIMS[0],
        resolution=resolution, copies=8, step=_RES // 2)


def _launch_body(resolution: str):
    # the streamed regime dispatches the same scan over one launch at a time
    from repro.core.launches import stacked_mttkrp
    return trace_jaxpr(
        stacked_mttkrp,
        _u32(_RES), _u32(_RES), _f32(_RES), _i32(_RES, len(_DIMS)),
        _factors(),
        re_fields=_FIELDS, re_shifts=_SHIFTS, mode=0, out_rows=_DIMS[0],
        resolution=resolution, copies=8, step=_RES)


def _fused(variant: str):
    from repro.kernels.fused import _fused_flat
    t = 2 * _RES
    return trace_jaxpr(
        _fused_flat,
        _u32(t), _u32(t), _f32(t), _i32(t, len(_DIMS)), _factors(),
        field_bits=_FIELDS, field_shifts=_SHIFTS, mode=0, out_rows=_DIMS[0],
        variant=variant, tile=_RES, interpret=False)


def _sweep():
    from repro.core.cp_als import sweep_mode_update
    grams = [_f32(_RANK, _RANK) for _ in _DIMS]
    return trace_jaxpr(sweep_mode_update, _f32(_DIMS[0], _RANK), grams,
                       mode=0)


def registered_hot_paths() -> list[HotPath]:
    """Every audited dataflow (late-bound so import stays cheap)."""
    return [
        HotPath("stacked_mttkrp[register]", "src/repro/core/launches.py",
                lambda: _stacked("register")),
        HotPath("stacked_mttkrp[hierarchical]", "src/repro/core/launches.py",
                lambda: _stacked("hierarchical")),
        HotPath("stacked_mttkrp[streamed]", "src/repro/core/streaming.py",
                lambda: _launch_body("register")),
        HotPath("_fused_flat[segment]", "src/repro/kernels/fused.py",
                lambda: _fused("segment")),
        HotPath("_fused_flat[stash]", "src/repro/kernels/fused.py",
                lambda: _fused("stash")),
        HotPath("sweep_mode_update", "src/repro/core/cp_als.py", _sweep),
    ]
