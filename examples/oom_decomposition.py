"""Out-of-memory decomposition: the paper's headline capability.

The tensor lives in HOST memory; only fixed-size launch reservations ever
occupy the device.  ``plan_for`` makes the regime decision: under a budget
smaller than the tensor's device footprint it returns a ``StreamedPlan``,
which overlaps H2D transfers of pending blocks with compute on active
blocks (paper §4.2 / §6.4.2) — and CP-ALS runs unmodified on top of the
plan, exactly as it would on an in-memory one.

    PYTHONPATH=src python examples/oom_decomposition.py
"""
import numpy as np

from repro import core
from repro.core.launches import launch_cache_bytes
from repro.engine import factor_bytes, in_memory_bytes, plan_for

# "amazon-like" scale-down: 170k nnz, 3 long modes (paper Table 2 analogue)
t = core.paper_like("amazon-like", seed=0)
print(f"tensor dims={t.dims} nnz={t.nnz:,}")

# deliberately tiny per-launch reservation -> many streamed launches,
# emulating a tensor far larger than device memory
b = core.build_blco(t, max_nnz_per_block=1 << 13)
# budget covers the factor working set and the scan's temporaries but only
# HALF the resident tensor -> stream
budget = factor_bytes(b.dims, 16, np.float32) + in_memory_bytes(b, 16) \
    - launch_cache_bytes(b) // 2
plan = plan_for(b, budget, rank=16, queues=4)
assert plan.backend == "streamed", plan.backend
print(f"budget {budget/1e6:.1f} MB cannot hold the "
      f"{in_memory_bytes(b, 16)/1e6:.1f} MB tensor + factors "
      f"-> backend={plan.backend!r}: {len(b.launches)} launches of "
      f"<= {plan.spec.nnz:,} nnz, {plan.device_bytes()/1e6:.1f} MB in flight")

res = core.cp_als(plan, t.dims, rank=16,
                  norm_x=float(np.linalg.norm(t.values)), iters=8, seed=1)
print("fits:", [f"{f:.4f}" for f in res.fits])

s = plan.stats()
print(f"engine stats: {s.launches} launches, "
      f"{s.h2d_bytes/1e6:.1f} MB H2D, "
      f"put {s.put_time_s:.2f}s / dispatch {s.dispatch_time_s:.2f}s / "
      f"device {s.device_time_s:.2f}s / total {s.total_time_s:.2f}s")
print("in-memory-throughput vs overall-throughput gap = host-device "
      "interconnect cost (paper Fig. 10)")
plan.close()
