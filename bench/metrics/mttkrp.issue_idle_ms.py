"""Device idle time inside the program's ``plan.issue`` spans, per MTTKRP
call (ms): the argument handling and jit dispatch of each in-memory MTTKRP,
before the device has the scan to run (``bench/spans.py``)."""

from bench import spans


def read(rec):
    sp = spans.program_spans(rec)
    calls = spans.count(sp, "plan.issue") if sp else 0
    if not calls:
        return None
    return spans.idle_inside(rec["trace"], sp, "plan.issue") / calls / 1e6
