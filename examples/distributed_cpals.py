"""Distributed CP-ALS over a device mesh (beyond-paper scale-out).

nnz shard over the `data` axis (one psum per mode), rank shards over the
`model` axis (zero-communication in MTTKRP). The mesh spans every device
JAX sees: the chips of a TPU host, or fake CPU devices.

With a mesh installed in ``repro.dist.context``, the engine's regime
decision routes MTTKRP execution through the sharded backend automatically:
``plan_for`` returns a ``ShardedPlan`` and CP-ALS runs on it unchanged.

    PYTHONPATH=src python examples/distributed_cpals.py     # TPU host
    PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/distributed_cpals.py                 # CPU
"""
import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import core
from repro.dist.context import set_mesh
from repro.engine import plan_for
from repro.launch.mesh import make_test_mesh

# rank splits in two where the device count is even; nnz over the rest
n_dev = len(jax.devices())
model = 2 if n_dev % 2 == 0 else 1
mesh = make_test_mesh((n_dev // model, model), ("data", "model"))
set_mesh(mesh)                 # active mesh -> plan_for routes to sharded
print(f"mesh: {dict(mesh.shape)} over {len(jax.devices())} devices")

t = core.random_tensor((300, 200, 150), 300_000, seed=0, dist="powerlaw")
b = core.build_blco(t)
print(f"tensor dims={t.dims} nnz={t.nnz:,}; BLCO blocks={len(b.blocks)}")

plan = plan_for(b, 1 << 30, rank=16)
assert plan.backend == "sharded", plan.backend
print(f"engine chose backend={plan.backend!r} "
      f"({plan.device_bytes()/1e6:.1f} MB sharded over the mesh)")

rank = 16
factor_sh = NamedSharding(mesh, P(None, "model"))
init = [jax.device_put(f, factor_sh)
        for f in core.init_factors(t.dims, rank, seed=1)]

res = core.cp_als(plan, t.dims, rank,
                  norm_x=float(np.linalg.norm(t.values)), iters=10,
                  factors=init)
print("fits:", [f"{f:.4f}" for f in res.fits])
print("factor sharding:", res.factors[0].sharding)
print("engine stats:", plan.stats().snapshot())
plan.close()
set_mesh(None)
