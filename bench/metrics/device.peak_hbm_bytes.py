"""Peak device memory in use over the run (B): ``memory_stats()
["peak_bytes_in_use"]`` read after the window."""


def read(rec):
    return rec["memory_peak_bytes"] or None
