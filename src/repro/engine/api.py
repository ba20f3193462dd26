"""The unified MTTKRP engine API: one ``ExecutionPlan`` for every regime.

The paper's headline property is that ONE implementation on ONE tensor copy
serves every mode in both the in-memory and out-of-memory regimes.  This
module is that property restated as an API: every way this repo can execute
an MTTKRP — device-resident, streamed through fixed reservations, sharded
over a mesh, or a baseline format for benchmark parity — is an
``ExecutionPlan`` with the same four methods.  Consumers (CP-ALS, the
multi-tenant service, benchmarks, examples) never pick a kernel path
directly; they hold a plan.

    plan.mttkrp(factors, mode)   -> (I_mode, R) result
    plan.device_bytes()          -> exact bytes the plan holds resident
                                    (hi + lo + vals + bases, padded)
    plan.stats()                 -> unified EngineStats
    plan.close()                 -> release device buffers; returns bytes freed

An ``MTTKRPEngine`` turns a BLCO tensor + a device budget into a plan; the
default engine (``repro.engine.plan_for``) implements the paper's regime
decision, and the service's ``ServiceEngine`` adds reservation/residency
pooling across tenants.
"""
from __future__ import annotations

from typing import Protocol, runtime_checkable

import jax.numpy as jnp
import numpy as np

from repro.core.blco import BLCOTensor
from repro.core.streaming import EngineStats


@runtime_checkable
class ExecutionPlan(Protocol):
    """A concrete, introspectable way to execute MTTKRPs for one tensor."""

    backend: str          # "in_memory" | "streamed" | "sharded" | "coo" | ...

    def mttkrp(self, factors, mode: int):
        """Mode-``mode`` MTTKRP of the planned tensor with ``factors``."""
        ...

    def device_bytes(self) -> int:
        """Exact device bytes this plan holds resident (incl. bases arrays)."""
        ...

    def stats(self) -> EngineStats:
        """Execution counters accumulated by this plan."""
        ...

    def close(self) -> int:
        """Release device buffers; returns the bytes freed."""
        ...


@runtime_checkable
class MTTKRPEngine(Protocol):
    """Turns a tensor + budget into an ExecutionPlan (the regime decision)."""

    def plan(self, blco: BLCOTensor, *, device_budget_bytes: int, rank: int,
             dtype) -> ExecutionPlan:
        ...


def factor_bytes(dims, rank: int, dtype) -> int:
    """Device working-set bytes of a rank-R MTTKRP around the tensor itself:
    the N factor matrices plus the largest-mode output accumulator."""
    item = np.dtype(dtype).itemsize
    return (sum(int(d) for d in dims) + max(int(d) for d in dims)) \
        * rank * item


def accumulator_itemsize(value_dtype, dtype) -> int:
    """Bytes per element of the MTTKRP accumulator (values promoted
    against the factor dtype, never downcast)."""
    return jnp.result_type(np.dtype(value_dtype), np.dtype(dtype)).itemsize


def in_memory_bytes(blco: BLCOTensor, rank: int, dtype=jnp.float32) -> int:
    """Predicted device need of an ``InMemoryPlan`` for ``blco`` at rank R:
    the stacked launch cache's hi + lo + vals + bases (L launches padded to
    the size-class reservation, exactly what ``DeviceBLCO`` holds) plus
    one scan step's temporaries at that rank."""
    from repro.core.launches import (default_reservation, launch_cache_bytes,
                                     scan_temp_bytes)
    if not blco.launches:
        return 0
    res = default_reservation(max(l.nnz for l in blco.launches))
    return launch_cache_bytes(blco) + scan_temp_bytes(
        res, blco.order, rank, accumulator_itemsize(blco.values.dtype, dtype))
