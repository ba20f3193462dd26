"""Share of its roofline the MTTKRP reaches (%): the least time of the
window's MTTKRP calls (``bench/work.py``, the compulsory bytes over HBM
bandwidth or the operations over peak FLOP/s, whichever is larger) over
the device time of the ``jit_stacked_mttkrp`` module in the trace."""

from bench import work

MTTKRP_MODULE = "jit_stacked_mttkrp"


def read(rec):
    tr = rec["trace"]
    if tr is None or not rec["call_modes"]:
        return None
    ns = tr.module_ns(MTTKRP_MODULE, [tr.window()])
    if not ns:
        return None
    least = sum(work.least_seconds(rec["dims"], rec["nnz"], rec["rank"],
                                   rec["value_dtype"], mode, rec["peaks"])
                for mode in rec["call_modes"])
    return 100.0 * least / (ns / 1e9)
