"""Process-wide dispatch accounting for MTTKRP execution paths.

A *dispatch* is one host->device invocation of a jitted compute callable
(the unit the paper's "kernel launching overhead" is paid in).  Every
MTTKRP path in this repo records its dispatches here, so tests and
benchmarks can assert launch-count claims directly:

* the legacy per-launch loop records one dispatch per BLCO launch;
* the launch-cache scan path records exactly ONE per ``mttkrp`` call;
* the fused Pallas path records exactly ONE per ``mttkrp`` call.

The counter is monotonic; callers snapshot it before/after
(``dispatch_count()``) rather than resetting, so concurrent readers never
race each other's deltas.  Beside it, every MTTKRP call records the
reduction it ran (``record_resolution``), so how often the MXU path engages
is a count (``resolution_counts()``).
"""
from __future__ import annotations

import collections

_dispatches = 0
_resolutions: collections.Counter = collections.Counter()


def record_dispatch(n: int = 1) -> None:
    """Record ``n`` host->device compute dispatches."""
    global _dispatches
    _dispatches += int(n)


def dispatch_count() -> int:
    """Monotonic count of compute dispatches recorded so far."""
    return _dispatches


def record_resolution(resolution: str) -> None:
    """Record one MTTKRP call whose reduction ran as ``resolution``."""
    _resolutions[resolution] += 1


def resolution_counts() -> dict:
    """Monotonic MTTKRP calls so far, per resolution (a copy)."""
    return dict(_resolutions)
