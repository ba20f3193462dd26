"""Host-side time of a CP-ALS sweep: each traced ``bench.sweep`` span less
the device time of the MTTKRP programs inside it (``jit_stacked_mttkrp``,
or ``jit__shard_fn`` over a mesh, averaged over its chips), averaged over
sweeps (ms).  It is the eager mode updates, fences and the fit's read-back."""

MTTKRP_MODULES = ("jit_stacked_mttkrp", "jit__shard_fn")


def read(rec):
    tr = rec["trace"]
    if tr is None:
        return None
    sweeps = tr.span_intervals("bench.sweep")
    if not sweeps or not tr.module_ns(MTTKRP_MODULES):
        return None
    outside = [(e - s) - tr.module_ns(MTTKRP_MODULES, [(s, e)])
               for s, e in sweeps]
    return sum(outside) / len(outside) / 1e6
