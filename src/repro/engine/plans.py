"""The four ExecutionPlan backends behind the unified engine API.

  InMemoryPlan   device-resident BLCO (absorbs ``core.mttkrp.DeviceBLCO``):
                 the paper's in-memory regime — one upload, then every
                 MTTKRP is a single jitted dispatch.
  StreamedPlan   host-resident BLCO streamed through fixed reservations
                 (absorbs ``OOMExecutor``/``stream_mttkrp``): the paper's
                 out-of-memory regime.
  ShardedPlan    nnz-sharded MTTKRP over a device mesh (routes through
                 ``core.distributed``): the beyond-paper scale-out regime.
  BaselinePlan   COO / F-COO / CSF device formats from ``core.baselines``,
                 for benchmark parity under the same API.

Every plan owns one ``EngineStats`` and reports its exact resident device
bytes — including the per-element bases arrays — so admission control can
reason about *measured* footprints instead of padded worst cases.
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.core import baselines
from repro.core.blco import BLCOTensor, decode_coords
from repro.obs import ledger as obs_ledger
from repro.obs import trace as obs_trace
from repro.core.mttkrp import DEFAULT_COPIES, DeviceBLCO, validate_kernel
from repro.core.streaming import (EngineStats, LaunchChunks, ReservationSpec,
                                  count_compiles, reservation_for,
                                  stream_mttkrp)
from repro.core.tensor import SparseTensor, from_coo

from .api import in_memory_bytes


class InMemoryPlan:
    """Device-resident plan: the whole BLCO tensor lives in device memory.

    The launch cache is built once at plan creation; every ``mttkrp`` call
    afterwards is exactly ONE jitted dispatch (``kernel="xla"``: a
    ``lax.scan`` over the stacked launches; ``kernel="pallas"``: the fused
    single-``pallas_call`` pipeline) with zero host-side work.  Calls are
    fenced (``block_until_ready``) so ``EngineStats`` records the same
    dispatch-vs-device timing split the streamed plan does.
    """

    backend = "in_memory"

    def __init__(self, blco: BLCOTensor, *, resolution: str = "auto",
                 copies: int = DEFAULT_COPIES, device: DeviceBLCO | None = None,
                 owns_device: bool = True, kernel: str = "xla"):
        validate_kernel(kernel)
        self.dims = blco.dims
        self.resolution = resolution
        self.copies = copies
        self.kernel = kernel
        self._owns_device = owns_device if device is not None else True
        self._dev: DeviceBLCO | None = device if device is not None \
            else DeviceBLCO(blco, kernel=kernel)
        self._stats = EngineStats(backend=self.backend)
        # kept for the analytic device-traffic model the ledger attributes
        self._nnz = blco.nnz
        self._order = blco.order
        self._value_itemsize = np.dtype(blco.values.dtype).itemsize
        if device is None:
            # the one H2D transfer of this regime: the initial upload
            self._stats.h2d_bytes += self._dev.device_bytes()
            if obs_ledger.LEDGER.enabled:
                # seconds=0.0 mirrors the stats exactly: the upload adds
                # bytes but no put_time_s in this regime
                obs_ledger.record(obs_ledger.HOST_DEVICE,
                                  self._dev.device_bytes(), 0.0,
                                  regime=self.backend)

    def mttkrp(self, factors, mode: int, *, resolution: str | None = None,
               copies: int | None = None):
        if self._dev is None:
            raise RuntimeError("plan is closed")
        with obs_trace.span("plan.mttkrp", "plan", backend=self.backend,
                            mode=mode), count_compiles(self._stats):
            t0 = time.perf_counter()
            with obs_trace.span("plan.issue", "plan", mode=mode):
                out = self._dev.mttkrp(
                    factors, mode, kernel=self.kernel,
                    resolution=resolution if resolution is not None
                    else self.resolution,
                    copies=copies if copies is not None else self.copies)
            # host wall time of the (async) dispatch vs the fenced device span
            t1 = time.perf_counter()
            self._stats.dispatch_time_s += t1 - t0
            self._stats.hist.dispatch_s.record(t1 - t0)
            with obs_trace.span("plan.fence", "plan", mode=mode):
                if hasattr(out, "block_until_ready"):
                    out.block_until_ready()
            t2 = time.perf_counter()
            self._stats.device_time_s += t2 - t0
            self._stats.total_time_s += t2 - t0
            self._stats.mttkrp_calls += 1
            self._stats.launches += 1        # one fused dispatch per call
            if obs_ledger.LEDGER.enabled:
                # fenced seconds (same t2 - t0 window as device_time_s);
                # HBM bytes attributed from the per-kernel model
                rank = factors[0].shape[1]
                obs_ledger.record(
                    obs_ledger.DEVICE_HBM,
                    obs_ledger.hbm_model_bytes(
                        self._nnz, order=self._order, rank=rank,
                        value_itemsize=self._value_itemsize,
                        factor_itemsize=np.dtype(factors[0].dtype).itemsize,
                        kernel=self.kernel),
                    t2 - t0, regime=self.backend,
                    flops=obs_ledger.mttkrp_flops(self._nnz,
                                                  order=self._order,
                                                  rank=rank))
        return out

    def device_bytes(self) -> int:
        return self._dev.device_bytes() if self._dev is not None else 0

    def stats(self) -> EngineStats:
        return self._stats

    def close(self) -> int:
        if self._dev is None:
            return 0
        freed = self._dev.device_bytes()
        if self._owns_device:
            self._dev.delete()
        self._dev = None
        return freed


class StreamedPlan:
    """Out-of-memory plan: host-resident tensor, fixed device reservations."""

    backend = "streamed"

    def __init__(self, blco: BLCOTensor, *, queues: int = 4,
                 reservation_nnz: int | None = None,
                 spec: ReservationSpec | None = None,
                 chunks: list | None = None,
                 resolution: str = "auto", copies: int = DEFAULT_COPIES,
                 kernel: str = "xla"):
        validate_kernel(kernel)
        self.blco = blco
        self.dims = blco.dims
        self.queues = queues
        self.resolution = resolution
        self.copies = copies
        self.kernel = kernel
        self.spec = spec if spec is not None \
            else reservation_for(blco, reservation_nnz)
        # chunks are padded LAZILY, one launch per pull inside the streaming
        # loop: the host window is O(queues x reservation), never all
        # launches resident (the paper's out-of-memory premise)
        self._chunks = chunks if chunks is not None \
            else LaunchChunks(blco, self.spec.nnz)
        self._stats = EngineStats(backend=self.backend)
        self._closed = False

    def mttkrp(self, factors, mode: int, *, resolution: str | None = None,
               copies: int | None = None):
        if self._closed:
            raise RuntimeError("plan is closed")
        with obs_trace.span("plan.mttkrp", "plan", backend=self.backend,
                            mode=mode), count_compiles(self._stats):
            return stream_mttkrp(
                self._chunks, self.blco, factors, mode, queues=self.queues,
                resolution=resolution if resolution is not None
                else self.resolution,
                copies=copies if copies is not None else self.copies,
                stats=self._stats, kernel=self.kernel)

    def device_bytes(self) -> int:
        """Reservation bytes in flight (the only device-resident state)."""
        return 0 if self._closed else self.spec.bytes_in_flight(self.queues)

    def host_window_bytes(self) -> int:
        """Padded host bytes the streaming loop holds at once (bounded by
        the queue depth — NOT the whole tensor's padded launches)."""
        return 0 if self._closed else \
            self.spec.bytes_per_launch * self.queues

    def stats(self) -> EngineStats:
        return self._stats

    def close(self) -> int:
        if self._closed:
            return 0
        freed = self.spec.bytes_in_flight(self.queues)
        self._chunks = None
        self._closed = True
        return freed


def sharded_bytes(blco: BLCOTensor, mesh, *, data_axis="data") -> int:
    """Predicted mesh-wide device bytes of a ShardedPlan for ``blco``.

    The nnz arrays are range-partitioned over the data axis but REPLICATED
    across the remaining mesh axes (``nnz_spec = P(data_axis)``), so the
    total resident footprint is the padded arrays times that replication
    factor.
    """
    from repro.core.distributed import shard_bytes
    if not blco.nnz:
        return 0
    data_size = 1
    for ax in (data_axis if isinstance(data_axis, tuple) else (data_axis,)):
        data_size *= mesh.shape[ax]
    replicas = mesh.size // data_size
    return shard_bytes(blco, data_size) * data_size * replicas


class ShardedPlan:
    """Mesh-sharded plan: nnz range-partitioned over the data axis."""

    backend = "sharded"

    def __init__(self, blco: BLCOTensor, mesh, *, data_axis="data",
                 model_axis="model"):
        from repro.core.distributed import make_distributed_mttkrp
        self.dims = blco.dims
        self.mesh = mesh
        self._nnz = blco.nnz
        self._value_dtype = blco.values.dtype
        self._device_bytes = sharded_bytes(blco, mesh, data_axis=data_axis)
        self._run = make_distributed_mttkrp(
            blco, mesh, data_axis=data_axis, model_axis=model_axis) \
            if blco.nnz else None
        self._stats = EngineStats(backend=self.backend)
        self._stats.h2d_bytes += self._device_bytes
        if obs_ledger.LEDGER.enabled:
            obs_ledger.record(obs_ledger.HOST_DEVICE, self._device_bytes,
                              0.0, regime=self.backend)
        self._closed = False

    def mttkrp(self, factors, mode: int):
        if self._closed:
            raise RuntimeError("plan is closed")
        self._stats.mttkrp_calls += 1
        self._stats.launches += 1
        with count_compiles(self._stats):
            if self._run is None:
                rank = factors[0].shape[1]
                # empty-tensor case at the promoted precision, matching the
                # sharded compute path (result_type of values vs factors)
                out_dtype = jnp.result_type(
                    jnp.asarray(np.zeros(0, self._value_dtype)), factors[0])
                return jnp.zeros((self.dims[mode], rank), out_dtype)
            return self._run(factors, mode)

    def device_bytes(self) -> int:
        return 0 if self._closed else self._device_bytes

    def bytes_by_device(self) -> dict:
        """Resident nnz-array bytes on each device of the mesh."""
        out: dict = {}
        if self._run is None:
            return out
        for arr in self._run.device_shards.values():
            for shard in arr.addressable_shards:
                out[shard.device] = out.get(shard.device, 0) \
                    + shard.data.nbytes
        return out

    def stats(self) -> EngineStats:
        return self._stats

    def close(self) -> int:
        if self._closed:
            return 0
        freed = self._device_bytes
        self._run = None          # drops the closure holding device shards
        self._closed = True
        return freed


_BASELINE_BUILDERS = {
    "coo": (baselines.COOFormat, baselines.DeviceCOO),
    "fcoo": (baselines.FCOOFormat, baselines.DeviceFCOO),
    "csf": (baselines.CSFFormat, baselines.DeviceCSF),
}

BASELINE_KINDS = tuple(_BASELINE_BUILDERS)


class BaselinePlan:
    """Baseline-format plan (COO / F-COO / CSF) for benchmark parity."""

    def __init__(self, device_fmt, kind: str):
        if kind not in _BASELINE_BUILDERS:
            raise ValueError(f"unknown baseline kind {kind!r}; "
                             f"expected one of {BASELINE_KINDS}")
        self.backend = kind
        self.dims = device_fmt.dims
        self._dev = device_fmt
        self._stats = EngineStats(backend=kind)
        self._stats.h2d_bytes += device_fmt.device_bytes()
        if obs_ledger.LEDGER.enabled:
            obs_ledger.record(obs_ledger.HOST_DEVICE,
                              device_fmt.device_bytes(), 0.0,
                              regime=self.backend)

    @classmethod
    def from_tensor(cls, t: SparseTensor, kind: str = "coo") -> "BaselinePlan":
        host_cls, dev_cls = _BASELINE_BUILDERS[kind]
        return cls(dev_cls(host_cls.build(t)), kind)

    @classmethod
    def from_blco(cls, blco: BLCOTensor, kind: str = "coo") -> "BaselinePlan":
        """Decode the BLCO encoding back to COO and build the baseline —
        the single stored copy really does carry the full coordinates."""
        t = from_coo(decode_coords(blco), np.asarray(blco.values), blco.dims)
        return cls.from_tensor(t, kind)

    def mttkrp(self, factors, mode: int):
        if self._dev is None:
            raise RuntimeError("plan is closed")
        self._stats.mttkrp_calls += 1
        with count_compiles(self._stats):
            return self._dev.mttkrp(factors, mode)

    def device_bytes(self) -> int:
        return self._dev.device_bytes() if self._dev is not None else 0

    def stats(self) -> EngineStats:
        return self._stats

    def close(self) -> int:
        if self._dev is None:
            return 0
        freed = self._dev.device_bytes()
        self._dev = None
        return freed


__all__ = ["InMemoryPlan", "StreamedPlan", "ShardedPlan", "BaselinePlan",
           "BASELINE_KINDS", "in_memory_bytes", "sharded_bytes"]
