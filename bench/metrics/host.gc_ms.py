"""Host time inside the program's ``python.gc`` spans, one per collection
by Python's collector, per sweep (ms); 0.0 when no collection ran in the
window (``bench/spans.py``)."""

from bench import spans


def read(rec):
    sp = spans.program_spans(rec)
    sweeps = spans.count(sp, "als.sweep") if sp else 0
    if not sweeps:
        return None
    return sum(e - s for s, e, n in sp if n == "python.gc") / sweeps / 1e6
