"""Share of its roofline the MTTKRP reaches (%): the least time of the
window's MTTKRP calls (``bench/work.py``, the compulsory bytes over HBM
bandwidth or the operations over peak FLOP/s, whichever is larger) at the
peaks of the cell's chips summed, over the device time of the MTTKRP
module (``jit_stacked_mttkrp``, or ``jit__shard_fn`` over a mesh, averaged
over its chips) in the trace."""

from bench import work

MTTKRP_MODULES = ("jit_stacked_mttkrp", "jit__shard_fn")


def read(rec):
    tr = rec["trace"]
    if tr is None or not rec["call_modes"]:
        return None
    ns = tr.module_ns(MTTKRP_MODULES, [tr.window()])
    if not ns:
        return None
    peaks = {k: rec["peaks"][k] * rec["chips"]
             for k in ("hbm_bytes_per_s", "flops_per_s")}
    least = sum(work.least_seconds(rec["dims"], rec["nnz"], rec["rank"],
                                   rec["value_dtype"], mode, peaks)
                for mode in rec["call_modes"])
    return 100.0 * least / (ns / 1e9)
