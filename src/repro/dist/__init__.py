"""Distributed substrate: mesh context and parameter sharding rules.

Split out of the model/launch layers so every consumer (models, launch,
trainer, benchmarks, tests) shares one source of truth:

* ``context``  — process-wide mesh registry + logical-axis activation
  constraints ("dp" = the data/ZeRO axes, "tp" = the model axis);
* ``sharding`` — shape-only parameter partition specs (ZeRO/TP planning
  that works on ``jax.eval_shape`` trees, no devices needed).
"""
from . import context, sharding

__all__ = ["context", "sharding"]
