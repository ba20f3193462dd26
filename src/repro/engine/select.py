"""``plan_for``: the paper's regime decision as a one-call auto-selector.

    sharded        a mesh context is active (repro.dist.context) or passed
                   in — multi-device capacity, route through
                   core.distributed;
    in_memory      the tensor's true device footprint (hi + lo + vals +
                   bases, padded) plus the rank-R factor working set and
                   one scan step's temporaries fits the budget — the
                   paper's in-memory regime, zero per-iteration H2D;
    disk_streamed  the tensor exceeds the HOST budget
                   (``host_budget_bytes``) — spill it to the persistent
                   store and stream mmap'd reservation chunks straight to
                   the device (one tier below the paper's OOM regime);
    streamed       otherwise — fixed reservations stream the host-resident
                   tensor (the paper's out-of-memory regime), provided the
                   in-flight reservation + scan temporaries + factor
                   working set fits;
    baselines      never auto-selected; request ``backend="coo"|"fcoo"|
                   "csf"`` explicitly for benchmark parity.

``DefaultEngine`` wraps the same decision behind the ``MTTKRPEngine``
protocol for callers that hold an engine rather than call ``plan_for``.
"""
from __future__ import annotations

import os
import tempfile

import jax.numpy as jnp

from repro.core.blco import BLCOTensor, format_bytes
from repro.core.mttkrp import DEFAULT_COPIES, validate_kernel
from repro.core.streaming import reservation_for
from repro.dist.context import get_mesh
from repro.faults import inject as faults
from repro.obs import trace as obs_trace

from .api import accumulator_itemsize, factor_bytes, in_memory_bytes
from .plans import (BASELINE_KINDS, BaselinePlan, InMemoryPlan, ShardedPlan,
                    StreamedPlan, sharded_bytes)

AUTO_BACKENDS = ("auto", "in_memory", "streamed", "disk_streamed",
                 "sharded") + BASELINE_KINDS


def plan_for(blco: BLCOTensor, device_budget_bytes: int, *, rank: int,
             dtype=jnp.float32, backend: str = "auto", mesh=None,
             queues: int = 4, reservation_nnz: int | None = None,
             tensor=None, resolution: str = "auto",
             copies: int = DEFAULT_COPIES, kernel: str = "xla",
             host_budget_bytes: int | None = None,
             store_path: str | None = None, sanitize: bool | None = None):
    """Build the ExecutionPlan for ``blco`` under ``device_budget_bytes``.

    ``tensor`` (the original SparseTensor) is only consulted for baseline
    backends; without it the coordinates are decoded from the BLCO copy.
    ``kernel`` selects the compute path for the in-memory and streamed
    regimes: ``"xla"`` (reference dataflow, scan over the launch cache) or
    ``"pallas"`` (fused single-``pallas_call`` pipeline, interpreted off
    the TPU; it does not compile for TPU and raises there).

    ``host_budget_bytes`` extends the regime decision one memory tier
    down: when the tensor's host footprint (``format_bytes``) exceeds it,
    the tensor is spilled to the persistent store at ``store_path`` (an
    anonymous temp file, deleted on ``plan.close()``, when not given) and
    a ``DiskStreamedPlan`` feeds the device from mmap'd chunks with an
    O(queues x reservation) host window.  Raises ValueError when no
    regime fits the budget.

    ``sanitize`` wraps the plan in the runtime sanitizer's contract
    checker (:mod:`repro.analysis.sanitize`): ``True``/``False`` force it
    on/off, ``None`` (default) follows ``REPRO_SANITIZE``.  Sanitized
    plans are bit-identical to plain ones — the wrapper only inspects
    inputs and outputs.
    """
    from repro.analysis.sanitize import wrap_plan
    with obs_trace.span("engine.plan_for", "plan", nnz=blco.nnz,
                        requested=backend) as sp:
        plan = _plan_for_impl(
            blco, device_budget_bytes, rank=rank, dtype=dtype,
            backend=backend, mesh=mesh, queues=queues,
            reservation_nnz=reservation_nnz, tensor=tensor,
            resolution=resolution, copies=copies, kernel=kernel,
            host_budget_bytes=host_budget_bytes,
            store_path=store_path)
        sp.set(backend=plan.backend)
        return wrap_plan(plan, enable=sanitize)


def _plan_for_impl(blco: BLCOTensor, device_budget_bytes: int, *, rank: int,
                   dtype, backend: str, mesh, queues: int,
                   reservation_nnz: int | None, tensor, resolution: str,
                   copies: int, kernel: str,
                   host_budget_bytes: int | None, store_path: str | None):
    if backend not in AUTO_BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; "
                         f"expected one of {AUTO_BACKENDS}")
    validate_kernel(kernel)
    if backend in BASELINE_KINDS:
        if kernel != "xla":
            raise ValueError(f"kernel={kernel!r} is not supported on "
                             f"baseline backends; use kernel='xla'")
        return BaselinePlan.from_tensor(tensor, backend) \
            if tensor is not None else BaselinePlan.from_blco(blco, backend)

    working = factor_bytes(blco.dims, rank, dtype)
    acc_itemsize = accumulator_itemsize(blco.values.dtype, dtype)
    mesh = mesh if mesh is not None else get_mesh()
    if backend == "sharded" or (backend == "auto" and mesh is not None):
        if mesh is None:
            raise ValueError("backend='sharded' requires an active mesh "
                             "(repro.dist.context.set_mesh) or mesh=...")
        if kernel != "xla":
            raise ValueError("kernel='pallas' is not supported on the "
                             "sharded backend yet; use kernel='xla'")
        need = sharded_bytes(blco, mesh) + working
        if need > device_budget_bytes:
            raise ValueError(
                f"sharded plan needs {need} B across the mesh "
                f"(tensor shards x replicas + factors) but the device "
                f"budget is {device_budget_bytes} B")
        return ShardedPlan(blco, mesh)

    # ------------------------------------------------------- regime builders
    # The three single-device regimes as closures over one kernel argument,
    # so the degradation ladder below can retry a rung with kernel="xla"
    # (pallas fallback) or fall one memory tier down on allocation failure.
    demotions: list[str] = []

    def _done(plan):
        if demotions:
            plan.stats().demotions += len(demotions)
        return plan

    def _build_in_memory(k):
        # (the plan.alloc fault probe fires inside LaunchCache.from_blco —
        # the regime's actual device-allocation moment)
        return InMemoryPlan(blco, resolution=resolution, copies=copies,
                            kernel=k)

    def _stream_need(spec):
        return (spec.bytes_in_flight(queues)
                + spec.temp_bytes(rank, acc_itemsize) + working)

    def _build_streamed(k):
        faults.maybe_fail("plan.alloc")
        spec = reservation_for(blco, reservation_nnz)
        if _stream_need(spec) > device_budget_bytes:
            raise ValueError(
                f"no regime fits the budget: streaming needs "
                f"{_stream_need(spec)} B in flight "
                f"(reservation {spec.nnz} nnz x {queues} queues + scan "
                f"temporaries + factors) but the device budget is "
                f"{device_budget_bytes} B")
        return StreamedPlan(blco, queues=queues, spec=spec,
                            resolution=resolution, copies=copies, kernel=k)

    def _build_disk(k):
        from repro.store import DiskStreamedPlan
        spec = reservation_for(blco, reservation_nnz)
        if _stream_need(spec) > device_budget_bytes:
            raise ValueError(
                f"disk-streamed plan needs {_stream_need(spec)} B in flight "
                f"(reservation {spec.nnz} nnz x {queues} queues + scan "
                f"temporaries + factors) but the device budget is "
                f"{device_budget_bytes} B")
        if store_path is None:
            fd, path = tempfile.mkstemp(suffix=".blco")
            os.close(fd)
            delete = True
        else:
            path, delete = store_path, False
        try:
            return DiskStreamedPlan.spill(
                blco, path, reservation_nnz=spec.nnz, delete_on_close=delete,
                queues=queues, resolution=resolution, copies=copies,
                kernel=k)
        except BaseException:
            if delete:              # don't orphan the anonymous spill file
                try:
                    os.unlink(path)
                except OSError:
                    pass
            raise

    if backend == "disk_streamed" or (
            backend == "auto" and host_budget_bytes is not None
            and format_bytes(blco) > host_budget_bytes):
        return _done(_kernel_fallback(_build_disk, kernel, demotions))

    # ---------------------------------------------------- degradation ladder
    # auto mode falls one memory tier per allocation failure:
    # in_memory -> streamed -> disk_streamed.  Explicit backends keep the
    # kernel fallback (pallas -> xla) but never change regime — the caller
    # asked for that tier by name.
    auto = backend == "auto"
    in_memory_need = in_memory_bytes(blco, rank, dtype) + working
    if backend == "in_memory" or (auto and in_memory_need
                                  <= device_budget_bytes):
        if in_memory_need > device_budget_bytes:
            raise ValueError(
                f"in-memory plan needs {in_memory_need} B (resident tensor "
                f"+ scan temporaries + factors) but the device budget is "
                f"{device_budget_bytes} B")
        try:
            return _done(_kernel_fallback(_build_in_memory, kernel,
                                          demotions))
        except Exception as exc:    # noqa: BLE001 — classified right below
            if not (auto and _is_alloc_failure(exc)):
                raise
            _note_demotion(demotions, "in_memory->streamed", exc)

    try:
        return _done(_kernel_fallback(_build_streamed, kernel, demotions))
    except Exception as exc:        # noqa: BLE001 — classified right below
        if not (auto and _is_alloc_failure(exc)):
            raise
        _note_demotion(demotions, "streamed->disk_streamed", exc)
    return _done(_kernel_fallback(_build_disk, kernel, demotions))


_is_alloc_failure = faults.is_alloc_failure


def _kernel_fallback(build, kernel: str, demotions: list):
    """``build(kernel)`` with the pallas -> xla rung of the ladder."""
    try:
        return build(kernel)
    except faults.KernelFailure as exc:
        if kernel != "pallas":
            raise
        _note_demotion(demotions, "pallas->xla", exc)
        return build("xla")


def _note_demotion(demotions: list, what: str, exc: BaseException) -> None:
    demotions.append(what)
    with obs_trace.span("engine.demote", "plan", demote=what,
                        error=repr(exc)):
        pass


class DefaultEngine:
    """MTTKRPEngine over ``plan_for`` with fixed streaming configuration."""

    def __init__(self, *, queues: int = 4, mesh=None, backend: str = "auto",
                 reservation_nnz: int | None = None, kernel: str = "xla",
                 host_budget_bytes: int | None = None):
        self.queues = queues
        self.mesh = mesh
        self.backend = backend
        self.reservation_nnz = reservation_nnz
        self.kernel = kernel
        self.host_budget_bytes = host_budget_bytes

    def plan(self, blco: BLCOTensor, *, device_budget_bytes: int, rank: int,
             dtype=jnp.float32):
        return plan_for(blco, device_budget_bytes, rank=rank, dtype=dtype,
                        backend=self.backend, mesh=self.mesh,
                        queues=self.queues,
                        reservation_nnz=self.reservation_nnz,
                        kernel=self.kernel,
                        host_budget_bytes=self.host_budget_bytes)
