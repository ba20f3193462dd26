"""``DiskStreamedPlan``: the fifth ExecutionPlan backend (disk -> device).

The paper's out-of-memory regime assumes the tensor fits in host RAM and
streams host -> device through fixed reservations.  This plan starts one
tier lower: the tensor lives in a ``.blco`` store file, and the H2D queue
is fed directly from mmap'd reservation-padded chunks — the host never
holds more than the streaming window (``queues`` padded launches), so
tensors larger than host RAM decompose under the same engine API.

Because the disk layout is reservation-padded with the *same* power-of-two
buckets the host-streaming regime uses, a disk-streamed plan hits the same
compiled launch executable (and, under the service, the same pooled
reservation shapes) as a host-streamed plan of the same spec.
"""
from __future__ import annotations

import os

from repro.core.mttkrp import DEFAULT_COPIES, validate_kernel
from repro.core.streaming import (EngineStats, ReservationSpec,
                                  count_compiles, stream_mttkrp)
from repro.obs import trace as obs_trace

from .format import StoredBLCO, open_blco, save_blco


class DiskStreamedPlan:
    """Disk-resident plan: mmap'd store chunks stream straight to device.

    ``stored`` is a :class:`~repro.store.format.StoredBLCO` or a path to
    one.  ``delete_on_close`` unlinks the file when the plan closes — the
    right setting for an anonymous spill the plan itself created
    (:meth:`spill`); registry-owned store files are kept.
    """

    backend = "disk_streamed"

    def __init__(self, stored: StoredBLCO | str | os.PathLike, *,
                 queues: int = 4, resolution: str = "auto",
                 copies: int = DEFAULT_COPIES, kernel: str = "xla",
                 spec: ReservationSpec | None = None,
                 delete_on_close: bool = False):
        validate_kernel(kernel)
        if not isinstance(stored, StoredBLCO):
            stored = open_blco(os.fspath(stored))
        self.stored = stored
        self.dims = stored.dims
        self.queues = queues
        self.resolution = resolution
        self.copies = copies
        self.kernel = kernel
        self.spec = spec if spec is not None else stored.spec
        self.delete_on_close = delete_on_close
        self._stats = EngineStats(backend=self.backend)
        self._closed = False

    @classmethod
    def spill(cls, blco, path: str, *, fingerprint: str | None = None,
              norm_x: float | None = None, reservation_nnz: int | None = None,
              delete_on_close: bool = True, **kwargs) -> "DiskStreamedPlan":
        """Write ``blco`` to ``path`` and plan disk-streaming from it.

        The host copy can be dropped afterwards; by default the spill file
        is private to this plan and unlinked on ``close()``.
        """
        save_blco(blco, path, fingerprint=fingerprint, norm_x=norm_x,
                  reservation_nnz=reservation_nnz)
        return cls(path, delete_on_close=delete_on_close, **kwargs)

    def mttkrp(self, factors, mode: int, *, resolution: str | None = None,
               copies: int | None = None):
        if self._closed:
            raise RuntimeError("plan is closed")
        with obs_trace.span("plan.mttkrp", "plan", backend=self.backend,
                            mode=mode), count_compiles(self._stats):
            return stream_mttkrp(
                self.stored.chunks(stats=self._stats), self.stored, factors,
                mode, queues=self.queues,
                resolution=resolution if resolution is not None
                else self.resolution,
                copies=copies if copies is not None else self.copies,
                stats=self._stats, kernel=self.kernel)

    def device_bytes(self) -> int:
        """Reservation bytes in flight (identical to the streamed regime)."""
        return 0 if self._closed else self.spec.bytes_in_flight(self.queues)

    def host_window_bytes(self) -> int:
        """Padded chunk bytes the host can hold at once: the queue window."""
        return 0 if self._closed else \
            self.spec.bytes_per_launch * self.queues

    def disk_bytes(self) -> int:
        """Size of the backing store file."""
        return 0 if self._closed else self.stored.file_bytes()

    def stats(self) -> EngineStats:
        return self._stats

    def close(self) -> int:
        if self._closed:
            return 0
        freed = self.spec.bytes_in_flight(self.queues)
        path = self.stored.path
        self.stored.close()
        self._closed = True
        if self.delete_on_close:
            try:
                os.unlink(path)
            except OSError:
                pass
        return freed
