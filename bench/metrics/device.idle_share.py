"""Share of the traced window in which no op ran on the device (%):
1 - union of the device's op intervals / the ``bench.window`` span."""


def read(rec):
    tr = rec["trace"]
    if tr is None or not tr.devices:
        return None
    w0, w1 = tr.window()
    busy = tr.busy_ns()
    if w1 <= w0 or not busy:
        return None
    return 100.0 * (1.0 - busy / (w1 - w0))
