"""Device time of one MTTKRP (ms): summed durations of the MTTKRP module's
executions in the traced window (``jit_stacked_mttkrp`` on one chip, the
sharded ``shard_map`` program ``jit__shard_fn`` over a mesh, averaged over
its chips), over the MTTKRP calls made in it."""

MTTKRP_MODULES = ("jit_stacked_mttkrp", "jit__shard_fn")


def read(rec):
    tr = rec["trace"]
    calls = len(rec["call_modes"])
    if tr is None or not calls:
        return None
    ns = tr.module_ns(MTTKRP_MODULES, [tr.window()])
    return ns / calls / 1e6 if ns else None
