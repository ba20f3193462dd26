"""Out-of-memory (OOM) MTTKRP: stream BLCO launches through device queues.

The paper (§4.2, §6.4.2) streams BLCO blocks host->device through up to 8
device queues, each with a fixed memory reservation, overlapping transfers of
pending blocks with compute on active blocks. The JAX adaptation:

* a fixed per-queue **reservation** = padded launch size, so every launch
  reuses the same compiled executable and the same device buffer shape
  (donated), exactly like the paper's reused queue reservations;
* **overlap** comes from JAX's async dispatch: we issue `jax.device_put` for
  up to ``queues`` launches ahead of the compute consuming them, so on a real
  accelerator H2D copies of pending launches run under compute of active ones
  (on this CPU container the mechanism is exercised, the overlap is measured
  on-device);
* the factor matrices and the (I_mode, R) accumulator are device-resident;
  only nnz data streams.

The building blocks (``ReservationSpec``, ``LaunchChunks``,
``stream_mttkrp``) are free-standing so higher layers can pool them:
``repro.service.executor`` streams many tenants' tensors through one shared
set of reservation shapes, reusing the same compiled executables.
``OOMExecutor`` is the single-tensor convenience wrapper.

``OOMExecutor.stats`` records bytes moved and per-phase wall time so the
Fig.-10 style benchmark can report overall vs in-memory throughput.

``repro.engine`` is the unified front door over this module: a
``StreamedPlan`` owns the reservation + chunks + an ``EngineStats`` and is
the one public way to execute a streamed MTTKRP; ``OOMExecutor`` remains as
the thin single-tensor convenience wrapper.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.faults import inject as faults
from repro.faults.retry import retry_call
from repro.obs import ledger as obs_ledger
from repro.obs import trace as obs_trace
from repro.obs.hist import EngineHists

from .blco import BLCOTensor
from .counters import record_dispatch
from .launches import scan_step_for, stacked_mttkrp
from .mttkrp import choose_resolution, DEFAULT_COPIES
from .padding import next_pow2 as _next_pow2


@dataclasses.dataclass
class EngineStats:
    """Unified per-plan execution counters (every engine backend fills one).

    Timing is split so async dispatch is never mistaken for device compute:
    ``dispatch_time_s`` is the host wall time spent issuing (possibly async)
    compute calls; ``device_time_s`` is the fenced span from the first compute
    dispatch of a call until ``block_until_ready()`` returns, i.e. it includes
    the actual device execution.  ``compute_time_s`` is kept as a deprecated
    read-only alias of ``device_time_s`` for pre-engine callers.

    ``hist`` keeps the per-event *distributions* behind the scalar totals
    (per-launch dispatch latency, per-chunk H2D and disk-fetch times,
    per-launch nnz — see :class:`repro.obs.hist.EngineHists`): the scalar
    sums equal the corresponding histogram sums by construction, the
    scalars stay for snapshot compatibility.
    """
    backend: str = ""
    mttkrp_calls: int = 0
    h2d_bytes: int = 0
    disk_bytes: int = 0          # disk->host bytes fetched (disk-streamed plans)
    launches: int = 0
    put_time_s: float = 0.0
    disk_time_s: float = 0.0     # host wall time fetching chunks from the store
    dispatch_time_s: float = 0.0
    device_time_s: float = 0.0
    total_time_s: float = 0.0
    retries: int = 0             # transient failures retried successfully
    giveups: int = 0             # retry budgets exhausted (error surfaced)
    demotions: int = 0           # regime/kernel fallbacks the ladder took
    compiles: int = 0            # backend compiles inside the plan's mttkrp
    hist: EngineHists = dataclasses.field(default_factory=EngineHists)

    @property
    def compute_time_s(self) -> float:
        return self.device_time_s

    def snapshot(self) -> dict:
        return {
            "backend": self.backend,
            "mttkrp_calls": self.mttkrp_calls,
            "h2d_bytes": self.h2d_bytes,
            "disk_bytes": self.disk_bytes,
            "launches": self.launches,
            "put_time_s": self.put_time_s,
            "disk_time_s": self.disk_time_s,
            "dispatch_time_s": self.dispatch_time_s,
            "device_time_s": self.device_time_s,
            "total_time_s": self.total_time_s,
            "retries": self.retries,
            "giveups": self.giveups,
            "demotions": self.demotions,
            "compiles": self.compiles,
            "hist": self.hist.snapshot(),
        }


@contextlib.contextmanager
def count_compiles(stats: EngineStats):
    """Add the backend compiles made inside the block to ``stats``."""
    before = obs_trace.compile_count()
    try:
        yield
    finally:
        stats.compiles += obs_trace.compile_count() - before


# Deprecated name: the streaming layer's ad-hoc stats object predates the
# unified engine API; all backends now share EngineStats.
StreamStats = EngineStats


@dataclasses.dataclass(frozen=True)
class ReservationSpec:
    """A fixed device launch-buffer shape (the paper's queue reservation).

    Every launch padded to this shape reuses one compiled executable and one
    device buffer footprint — the unit the service's admission control and
    executor pooling reason about.
    """
    nnz: int                 # padded slots per launch buffer
    order: int               # tensor order (bases array width)
    value_itemsize: int      # bytes per value

    @property
    def bytes_per_launch(self) -> int:
        """Device bytes of one in-flight launch (hi + lo + vals + bases)."""
        return self.nnz * (4 + 4 + self.value_itemsize + 4 * self.order)

    def bytes_in_flight(self, queues: int) -> int:
        """Device bytes of ``queues`` launch buffers in flight (the pooled
        reservation; the scan's per-call temporaries are counted apart,
        by :meth:`temp_bytes`)."""
        return self.bytes_per_launch * queues

    def temp_bytes(self, rank: int, itemsize: int) -> int:
        """Device temporaries of the scan over one launch at rank ``rank``."""
        from .launches import scan_temp_bytes
        return scan_temp_bytes(self.nnz, self.order, rank, itemsize)


def reservation_for(blco: BLCOTensor,
                    reservation_nnz: int | None = None) -> ReservationSpec:
    """Reservation covering the largest launch (pow2-padded unless given)."""
    max_launch = max((l.nnz for l in blco.launches), default=1)
    nnz = int(reservation_nnz or _next_pow2(max_launch))
    if nnz < max_launch:
        raise ValueError("reservation smaller than largest launch")
    return ReservationSpec(nnz=nnz, order=blco.order,
                           value_itemsize=blco.values.dtype.itemsize)


class LaunchChunks:
    """Lazily padded reservation chunks of a host-resident BLCO (re-iterable).

    Each iteration pads ONE launch at a time to the reservation size, so the
    streaming loop's host overhead is O(queues x reservation) padded buffers
    in flight instead of all launches resident at once (the pre-store code
    eagerly materialized every padded launch up front, which made the "OOM"
    path require more host memory than the tensor itself).  Zero-padding is
    exact for MTTKRP: pad slots delinearize to coordinate 0 with value 0,
    contributing +0.0 to row 0.

    ``pads`` counts chunk materializations — the regression observable that
    construction does no padding work and each ``mttkrp`` call pads exactly
    ``len(self)`` chunks.
    """

    def __init__(self, blco: BLCOTensor, reservation_nnz: int):
        r = int(reservation_nnz)
        max_launch = max((l.nnz for l in blco.launches), default=0)
        if max_launch > r:
            raise ValueError(f"launch of {max_launch} nnz exceeds "
                             f"reservation {r}")
        self.blco = blco
        self.reservation_nnz = r
        self._bases_all = blco.block_upper_bases()
        self._block_ids = blco.element_block_ids()
        self.pads = 0

    def __len__(self) -> int:
        return len(self.blco.launches)

    def chunk(self, i: int):
        """Pad launch ``i`` to the reservation (one fresh numpy tuple)."""
        b = self.blco
        r = self.reservation_nnz
        launch = b.launches[i]
        s, e = launch.start, launch.end
        n = e - s
        hi = np.zeros(r, np.uint32); hi[:n] = b.idx_hi[s:e]
        lo = np.zeros(r, np.uint32); lo[:n] = b.idx_lo[s:e]
        vals = np.zeros(r, b.values.dtype); vals[:n] = b.values[s:e]
        bases = np.zeros((r, b.order), np.int32)
        bases[:n] = self._bases_all[self._block_ids[s:e]]
        self.pads += 1
        return (hi, lo, vals, bases, n)

    def __iter__(self):
        for i in range(len(self)):
            yield self.chunk(i)


def prepare_chunks(blco: BLCOTensor, reservation_nnz: int):
    """Pad every launch to the reservation size, materialized as a list.

    The eager variant of :class:`LaunchChunks` — the in-memory regime's
    launch cache genuinely needs every padded launch at once (it stacks
    them); streaming callers should hold a ``LaunchChunks`` instead.
    """
    return list(LaunchChunks(blco, reservation_nnz))


def stream_mttkrp(chunks, blco: BLCOTensor, factors, mode: int, *,
                  queues: int, resolution: str = "auto",
                  copies: int = DEFAULT_COPIES,
                  stats: StreamStats | None = None,
                  kernel: str = "xla"):
    """Stream reservation chunks through the launch kernel.

    Keeps up to ``queues`` H2D transfers in flight ahead of compute (the
    paper's queue overlap). ``chunks`` is any (re-)iterable of
    ``(hi, lo, vals, bases, n)`` tuples that all share one reservation
    shape, so every launch hits the same compiled executable — a lazily
    padding :class:`LaunchChunks` (host-resident tensor), a disk-backed
    ``repro.store`` chunk source (mmap'd slices), or a plain list.  Chunks
    are pulled one at a time, so the host-side window never exceeds the
    ``queues`` transfers in flight.  ``kernel`` selects the per-chunk
    compute: the XLA reference dataflow or the fused single-``pallas_call``
    pipeline (``repro.kernels.fused``).
    """
    b = blco
    if resolution == "auto":
        resolution = choose_resolution(b.dims[mode])
    from .mttkrp import validate_kernel
    validate_kernel(kernel)
    if kernel == "pallas":
        from repro.kernels.fused import fused_mttkrp_flat
    factors = tuple(jnp.asarray(f) for f in factors)
    rank = factors[0].shape[1]
    # accumulate at the promoted precision (f64 values vs f32 factors must
    # not downcast); ``b`` is a BLCOTensor or a StoredBLCO — the empty
    # asarray canonicalizes the value dtype under the active x64 setting
    val_dtype = getattr(b, "value_dtype", None)
    if val_dtype is None:
        val_dtype = b.values.dtype
    out_dtype = jnp.result_type(jnp.asarray(np.zeros(0, val_dtype)),
                                factors[0])
    out = jnp.zeros((b.dims[mode], rank), out_dtype)
    stats = stats if stats is not None else StreamStats()
    step = None                       # the reservation's scan step, once known

    t_start = time.perf_counter()
    in_flight: list[tuple] = []
    t_first_dispatch: float | None = None
    nnz_total = 0                     # true nnz launched, for the HBM model

    def _issue(chunk):
        t0 = time.perf_counter()
        hi, lo, vals, bases, n = chunk

        def _put():
            faults.maybe_fail("stream.h2d")
            return (jax.device_put(hi), jax.device_put(lo),
                    jax.device_put(vals), jax.device_put(bases))

        # transient put failures (injected or genuine transport flake)
        # retry with backoff; the reservation shapes make a re-put
        # side-effect-free
        dev = retry_call(_put, site="stream.h2d", stats=stats)
        t1 = time.perf_counter()
        nbytes = hi.nbytes + lo.nbytes + vals.nbytes + bases.nbytes
        stats.put_time_s += t1 - t0
        stats.h2d_bytes += nbytes
        stats.hist.put_chunk_s.record(t1 - t0)
        stats.hist.launch_nnz.record(n)
        if obs_trace.TRACING.enabled:
            obs_trace.add_event("h2d.put", "h2d", t0, t1, bytes=nbytes, nnz=n)
        if obs_ledger.LEDGER.enabled:
            # same nbytes / t1 - t0 that fed the stats counters above:
            # the ledger's host_device account conserves put_time_s /
            # h2d_bytes exactly, by construction
            obs_ledger.record(obs_ledger.HOST_DEVICE, nbytes, t1 - t0,
                              regime=stats.backend)
        return dev, n

    def _consume(item):
        nonlocal out, t_first_dispatch, nnz_total, step
        (hi, lo, vals, bases), n = item
        t0 = time.perf_counter()
        if t_first_dispatch is None:
            t_first_dispatch = t0
        if kernel == "pallas":
            # fused_mttkrp_flat records its own dispatch
            out = out + fused_mttkrp_flat(
                hi, lo, vals, bases, factors,
                field_bits=b.re.field_bits, field_shifts=b.re.field_shift,
                mode=mode, out_rows=b.dims[mode], resolution=resolution)
        else:
            if step is None:
                step = scan_step_for(int(hi.shape[0]), b.order, rank,
                                     np.dtype(out_dtype).itemsize)
            record_dispatch()
            out = out + stacked_mttkrp(
                hi, lo, vals, bases, factors,
                re_fields=b.re.field_bits, re_shifts=b.re.field_shift,
                mode=mode, out_rows=b.dims[mode],
                resolution=resolution, copies=copies, step=step)
        # host wall time of the (async) dispatch only — NOT device compute
        t1 = time.perf_counter()
        stats.dispatch_time_s += t1 - t0
        stats.hist.dispatch_s.record(t1 - t0)
        stats.launches += 1
        nnz_total += int(n)
        if obs_trace.TRACING.enabled:
            obs_trace.add_event("dispatch.launch", "dispatch", t0, t1, nnz=n)

    for chunk in chunks:
        # keep up to `queues` transfers in flight ahead of compute
        in_flight.append(_issue(chunk))
        if len(in_flight) >= queues:
            _consume(in_flight.pop(0))
    while in_flight:
        _consume(in_flight.pop(0))
    out.block_until_ready()
    t_end = time.perf_counter()
    if t_first_dispatch is not None:
        # fenced: first dispatch -> all launches retired on device
        stats.device_time_s += t_end - t_first_dispatch
        if obs_trace.TRACING.enabled:
            obs_trace.add_event("device.fence", "device",
                                t_first_dispatch, t_end,
                                launches=stats.launches)
        if obs_ledger.LEDGER.enabled:
            # fenced seconds are measured (same window as device_time_s);
            # HBM bytes are model-attributed over the true nnz launched
            obs_ledger.record(
                obs_ledger.DEVICE_HBM,
                obs_ledger.hbm_model_bytes(
                    nnz_total, order=b.order, rank=rank,
                    value_itemsize=np.dtype(val_dtype).itemsize,
                    factor_itemsize=np.dtype(factors[0].dtype).itemsize,
                    kernel=kernel),
                t_end - t_first_dispatch, regime=stats.backend,
                flops=obs_ledger.mttkrp_flops(nnz_total, order=b.order,
                                              rank=rank))
    stats.mttkrp_calls += 1
    stats.total_time_s += t_end - t_start
    return out


class OOMExecutor:
    """Streams a (host-resident) BLCO tensor through fixed device reservations."""

    def __init__(self, blco: BLCOTensor, *, queues: int = 4,
                 reservation_nnz: int | None = None, kernel: str = "xla"):
        self.blco = blco
        self.queues = queues
        self.kernel = kernel
        self.spec = reservation_for(blco, reservation_nnz)
        self._prepared = LaunchChunks(blco, self.spec.nnz)
        self.stats = EngineStats(backend="streamed")

    @property
    def reservation(self) -> int:
        return self.spec.nnz

    def mttkrp(self, factors, mode: int, *, resolution: str = "auto",
               copies: int = DEFAULT_COPIES):
        return stream_mttkrp(self._prepared, self.blco, factors, mode,
                             queues=self.queues, resolution=resolution,
                             copies=copies, stats=self.stats,
                             kernel=self.kernel)
