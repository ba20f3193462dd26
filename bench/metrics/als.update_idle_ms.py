"""Device idle time inside the program's ``als.update`` spans, per sweep,
all modes together (ms): the gaps between the eager device ops of the
CP-ALS mode updates (``bench/spans.py``)."""

from bench import spans


def read(rec):
    sp = spans.program_spans(rec)
    sweeps = spans.count(sp, "als.sweep") if sp else 0
    if not sweeps:
        return None
    return spans.idle_inside(rec["trace"], sp, "als.update") / sweeps / 1e6
