"""Device time of the exchange between chips in one sharded MTTKRP (ms):
summed durations of the ``all-reduce`` ops (the ``psum`` over the data
axis) inside the sharded module (``jit__shard_fn``) in the traced window,
averaged over the mesh's devices, over the MTTKRP calls made in it."""

SHARDED_MODULE = "jit__shard_fn"


def read(rec):
    tr = rec["trace"]
    calls = len(rec["call_modes"])
    if tr is None or not calls:
        return None
    ns = tr.op_ns(SHARDED_MODULE, "all-reduce", [tr.window()])
    return ns / calls / 1e6 if ns else None
