"""Device idle time inside the program's ``als.fit`` span, per sweep (ms):
the fit's eager device ops and its read-back to the host
(``bench/spans.py``)."""

from bench import spans


def read(rec):
    sp = spans.program_spans(rec)
    sweeps = spans.count(sp, "als.sweep") if sp else 0
    if not sweeps:
        return None
    return spans.idle_inside(rec["trace"], sp, "als.fit") / sweeps / 1e6
