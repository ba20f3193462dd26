"""Device idle time inside the program's ``plan.fence`` spans, per MTTKRP
call (ms): from the scan's end on the device until ``block_until_ready``
returns and the mode update can be issued, with any runtime stall in that
wait (``bench/spans.py``)."""

from bench import spans


def read(rec):
    sp = spans.program_spans(rec)
    calls = spans.count(sp, "plan.fence") if sp else 0
    if not calls:
        return None
    return spans.idle_inside(rec["trace"], sp, "plan.fence") / calls / 1e6
