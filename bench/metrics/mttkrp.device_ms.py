"""Device time of one MTTKRP (ms): summed durations of the
``jit_stacked_mttkrp`` module's executions in the traced window, over the
MTTKRP calls made in it."""

MTTKRP_MODULE = "jit_stacked_mttkrp"


def read(rec):
    tr = rec["trace"]
    calls = len(rec["call_modes"])
    if tr is None or not calls:
        return None
    ns = tr.module_ns(MTTKRP_MODULE, [tr.window()])
    return ns / calls / 1e6 if ns else None
