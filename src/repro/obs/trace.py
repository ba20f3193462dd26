"""Lightweight span tracer: where the time goes, per pipeline stage.

The paper's claims are about *attribution* — kernel-launch overhead vs
memory-access irregularity vs streaming overlap — so flat time totals are
not enough: the streamed regimes interleave disk reads, H2D puts, and
device dispatches, and only a timeline shows whether they overlap.  This
module records that timeline as **spans**: named intervals on a *track*
(one track per pipeline stage: ``store`` / ``h2d`` / ``dispatch`` /
``device`` / ``scheduler`` / ``registry`` / ``plan`` / ``als`` /
``blco``), each carrying attributes like ``nnz``, ``launch``, ``bytes``.

Two recording APIs:

* :func:`span` — a context manager for code whose interval the tracer
  itself measures (plan ``mttkrp`` calls, scheduler quanta, registry
  spill/load).  Nesting is tracked through a :mod:`contextvars` variable,
  so a child span records its parent's name; contexts are per-thread, so
  spans emitted inside the service runtime's worker thread nest under the
  quantum span that thread opened — no cross-thread leakage.
* :func:`add_event` — records an interval the caller ALREADY measured
  (the streaming hot loop times every put/dispatch for ``EngineStats``
  anyway; tracing reuses those exact timestamps, so span sums and stats
  totals agree by construction).

Two sinks.  Every span also enters a ``jax.profiler.TraceAnnotation`` of
its name and attributes for its extent, whether or not the ring buffer
records: JAX keeps it only while a profile is being taken, on the same
clock as the device ops, so the program's layers name the device's idle
gaps in an ``.xplane.pb``.  ``add_event`` intervals, measured before
they are recorded, cannot reach the profiler.  JAX is imported at the first span, so this module
imports without it; without JAX, a span the ring buffer does not record is
a shared no-op singleton.

The ring buffer records only while ``TRACING.enabled``; hot paths guard
``add_event`` calls on the same flag.  Completed spans land in a
thread-safe bounded ring buffer (oldest evicted first, ``TRACING.dropped``
counts evictions); export them with :mod:`repro.obs.export`.

Two hooks of the host runtime report beside the spans: Python's collector
(a ``python.gc`` profiler annotation per collection, from
``gc.callbacks``; the ring buffer does not record it) and JAX's backend
compiles (counted by :func:`compile_count`, and a ``jit.compile`` event
under the span open at the time).
"""
from __future__ import annotations

import contextvars
import gc
import threading
import time
from collections import deque

DEFAULT_CAPACITY = 65536        # spans held in the ring buffer


class TracerState:
    """The module-level tracer: enable flag + bounded span ring buffer."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.enabled = False
        self.lock = threading.Lock()
        self.buf: deque = deque(maxlen=int(capacity))
        self.dropped = 0             # spans evicted by the bounded ring
        self.epoch_s = time.perf_counter()   # trace time zero (export origin)


# THE module-level state; hot paths read ``TRACING.enabled`` once per span.
TRACING = TracerState()

# Current span of this thread/context (contextvars are per-thread, so the
# runtime worker's quantum span parents only spans opened on that thread).
_current: contextvars.ContextVar = contextvars.ContextVar(
    "repro_obs_current_span", default=None)


class Span:
    """One named interval on a track, with attributes and a parent name,
    recorded into the ring buffer on exit when ``record`` is set."""

    __slots__ = ("name", "track", "attrs", "start_s", "end_s", "parent",
                 "_token", "_ann", "_keep")

    def __init__(self, name: str, track: str, attrs: dict,
                 record: bool = True):
        self.name = name
        self.track = track
        self.attrs = attrs
        self.start_s = 0.0
        self.end_s = 0.0
        self.parent: str | None = None
        self._token = None
        self._ann = None
        self._keep = record

    @property
    def duration_s(self) -> float:
        return max(0.0, self.end_s - self.start_s)

    def set(self, **attrs) -> "Span":
        """Attach attributes discovered mid-span (e.g. the chosen backend)."""
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**attrs)
        return self

    def __enter__(self) -> "Span":
        parent = _current.get()
        self.parent = parent.name if parent is not None else None
        self._token = _current.set(self)
        self._ann = _annotate(self.name, self.attrs)
        self.start_s = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_s = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._token is not None:
            _current.reset(self._token)
            self._token = None
        if self._keep:
            _record(self)
        return False

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, track={self.track!r}, "
                f"dur={self.duration_s * 1e6:.1f}us, attrs={self.attrs})")


class _NullSpan:
    """The shared span where nothing records: every operation is a no-op."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs):
        return self


_NULL = _NullSpan()

# ``jax.profiler.TraceAnnotation`` once JAX is imported (first span); False
# where JAX is missing.
_annotation_cls = None
_init_lock = threading.Lock()


def _profiler():
    """The annotation class, importing JAX and registering the compile
    listener on first use (once, whichever thread gets there first)."""
    global _annotation_cls
    if _annotation_cls is None:
        with _init_lock:
            if _annotation_cls is None:
                try:
                    import jax.monitoring
                    from jax.profiler import TraceAnnotation
                except ImportError:
                    _annotation_cls = False
                else:
                    jax.monitoring.register_event_duration_secs_listener(
                        _on_compile)
                    _annotation_cls = TraceAnnotation
    return _annotation_cls


def _annotate(name: str, attrs: dict):
    """An entered profiler annotation of ``name`` (None without JAX)."""
    cls = _profiler()
    if not cls:
        return None
    ann = cls(name, **attrs)
    ann.__enter__()
    return ann


def _record(s: Span) -> None:
    with TRACING.lock:
        if len(TRACING.buf) == TRACING.buf.maxlen:
            TRACING.dropped += 1
        TRACING.buf.append(s)


# ------------------------------------------------------------------ recording
def span(name: str, track: str = "main", **attrs):
    """Context manager over ``name`` on ``track`` while entered: a profiler
    annotation always, a ring-buffer span while tracing is enabled.

    Without JAX and with tracing disabled it is the shared no-op singleton.
    """
    if TRACING.enabled or _profiler():
        return Span(name, track, attrs, record=TRACING.enabled)
    return _NULL


def timed(name: str, track: str = "main", **attrs) -> Span:
    """Like :func:`span`, but a :class:`Span` even without JAX and with
    tracing disabled, so its ``duration_s`` can fill a counter beside it
    with the same clock reads (``BLCOTensor.construction_stats``, which is
    filled whether or not anything traces)."""
    return Span(name, track, attrs, record=TRACING.enabled)


def add_event(name: str, track: str, start_s: float, end_s: float,
              **attrs) -> None:
    """Record an interval the caller already measured (hot-loop path).

    The streaming loop times every chunk put / launch dispatch for
    ``EngineStats``; passing those timestamps here makes the trace agree
    with the stats *exactly*.  Hot paths should guard the call on
    ``TRACING.enabled`` so the disabled path does not even build kwargs.
    """
    if not TRACING.enabled:
        return
    s = Span(name, track, attrs)
    s.start_s = start_s
    s.end_s = end_s
    parent = _current.get()
    s.parent = parent.name if parent is not None else None
    _record(s)


def current_span():
    """The innermost entered span of this thread/context (or None)."""
    return _current.get()


# ------------------------------------------------------------------- control
def enable(capacity: int | None = None) -> None:
    """Turn span recording on (optionally resizing the ring buffer)."""
    with TRACING.lock:
        if capacity is not None and int(capacity) != TRACING.buf.maxlen:
            TRACING.buf = deque(TRACING.buf, maxlen=int(capacity))
        TRACING.enabled = True


def disable() -> None:
    # writes to the singleton go under its lock (hot-path READS of
    # ``TRACING.enabled`` stay lock-free by design: a stale read is a
    # dropped span, a torn enable/resize sequence would be corruption)
    with TRACING.lock:
        TRACING.enabled = False


def is_enabled() -> bool:
    return TRACING.enabled


def clear() -> None:
    """Drop all recorded spans and reset the export time origin."""
    with TRACING.lock:
        TRACING.buf.clear()
        TRACING.dropped = 0
        TRACING.epoch_s = time.perf_counter()


def spans() -> list:
    """Snapshot of the recorded spans (oldest first); buffer unchanged."""
    with TRACING.lock:
        return list(TRACING.buf)


def drain() -> list:
    """Remove and return all recorded spans (oldest first)."""
    with TRACING.lock:
        out = list(TRACING.buf)
        TRACING.buf.clear()
        return out


class enabled:
    """``with obs.trace.enabled(): ...`` — scoped tracing for tests/benches."""

    def __init__(self, capacity: int | None = None):
        self.capacity = capacity
        self._was = False

    def __enter__(self):
        self._was = TRACING.enabled
        enable(self.capacity)
        return self

    def __exit__(self, *exc) -> bool:
        with TRACING.lock:
            TRACING.enabled = self._was
        return False


# ----------------------------------------------------------- runtime hooks
_gc_open: list = []          # the annotation of the collection under way


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: a ``python.gc`` annotation per collection,
    for the profiler only (the ring buffer keeps what the program opened).
    Never imports JAX: a span must have loaded it first."""
    if phase == "start":
        if _annotation_cls:
            ann = _annotation_cls("python.gc",
                                  generation=info["generation"])
            ann.__enter__()
            _gc_open.append(ann)
    elif _gc_open:
        ann = _gc_open.pop()
        ann.set_metadata(collected=info["collected"])
        ann.__exit__(None, None, None)


gc.callbacks.append(_on_gc)

_compiles = 0


def _on_compile(event: str, secs: float, **_) -> None:
    """JAX monitoring listener: counts backend compiles; inside an open
    span it also records a ``jit.compile`` event under it."""
    global _compiles
    if event != "/jax/core/compile/backend_compile_duration":
        return
    _compiles += 1
    if TRACING.enabled and _current.get() is not None:
        end = time.perf_counter()
        add_event("jit.compile", "jit", end - secs, end, seconds=secs)


def compile_count() -> int:
    """Backend compiles made in this process since the listener was
    registered (at the first span, or here)."""
    _profiler()
    return _compiles
