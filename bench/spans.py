"""The program's own spans in a run's trace, and the device's idle time
inside them.

The program (``repro.obs.trace``) enters a ``jax.profiler.TraceAnnotation``
at each layer boundary: ``als.sweep`` > ``als.mttkrp`` > ``plan.mttkrp`` >
``plan.issue`` > ``launch_cache.scan``, then ``plan.fence``; ``als.update``
and ``als.fit`` per sweep; ``python.gc`` for each collection.  They sit on
the Python thread's line of the host plane, on the device ops' clock.
``xplane.load`` keeps only the benchmark's ``bench.*`` spans and the record
holds no trace path, so the readers find the run's trace themselves: the
newest ``.xplane.pb`` under ``<checkout>/.traces``, which ``run.py`` has
just written, parsed once per file, and used only if its ``bench.window``
is the record's to the nanosecond (a newer trace of another run is not
this one).  In a trace without the program's spans, or without the
record's window, every reader returns None.

    python3 bench/spans.py [trace.xplane.pb]

prints, for that trace (default: the newest), the device's idle time in
the window per sweep, split by the innermost program span over it, and the
longest idle gaps with the spans they sat in.
"""
from __future__ import annotations

import functools
import json
import os
import sys
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import xplane                                    # noqa: E402

TRACES = Path(__file__).resolve().parents[1] / ".traces"
PREFIXES = ("als.", "plan.", "launch_cache.", "python.", "blco.", "engine.")
OUTSIDE = "outside"
TOP = 10


def newest_trace() -> str | None:
    return xplane.find_xplane(str(TRACES))


@functools.lru_cache(maxsize=4)
def _parse(path: str, mtime: float) -> tuple:
    """``(windows, spans)`` of a trace: the ``bench.window`` intervals and
    the program spans ``(start_ns, end_ns, name)`` of the host line that
    holds ``bench.window`` (every host line where none does)."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    lines = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            lines.extend(xplane._events(line) for line in plane.lines)
    own = [ev for ev in lines if any(n == "bench.window" for _, _, n in ev)]
    windows = tuple((s, e) for ev in own for s, e, n in ev
                    if n == "bench.window")
    return windows, tuple(sorted(e for ev in (own or lines) for e in ev
                                 if e[2].startswith(PREFIXES)))


def program_spans(rec, path: str | None = None) -> list | None:
    """The program's spans inside the window, clipped to it; None without a
    device trace, when the trace file does not hold the record's window, or
    without any program span in the window."""
    tr = rec.get("trace")
    path = path or newest_trace()
    if tr is None or not tr.ops or not path:
        return None
    w0, w1 = tr.window()
    windows, program = _parse(path, os.path.getmtime(path))
    if (w0, w1) not in windows:
        return None
    out = [(max(s, w0), min(e, w1), n) for s, e, n in program
           if e > w0 and s < w1]
    return out or None


def count(spans, name: str) -> int:
    return sum(1 for _, _, n in spans if n == name)


def idle(ops, w0, w1) -> list:
    """Idle intervals of one device's ops inside ``[w0, w1]``."""
    out, t = [], w0
    for s, e in xplane._merge([(max(s, w0), min(e, w1)) for s, e, _ in ops
                               if e > w0 and s < w1]):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if w1 > t:
        out.append((t, w1))
    return out


def _intersections(a, b):
    """``(length, j)`` of each non-empty intersection of an interval of
    ``a`` with ``b[j]``; both sorted lists of disjoint intervals."""
    i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            yield hi - lo, j
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1


def idle_inside(tr, spans, name: str) -> float:
    """Device idle ns inside the spans called ``name`` (their children
    included), averaged over devices."""
    w0, w1 = tr.window()
    where = xplane._merge([(s, e) for s, e, n in spans if n == name])
    return sum(n for ops in tr.ops
               for n, _ in _intersections(idle(ops, w0, w1), where)) \
        / len(tr.ops)


def innermost(spans, w0, w1) -> list:
    """``[(a, b, name)]`` covering ``[w0, w1]``: each piece named by the
    innermost span over it, ``OUTSIDE`` where there is none.  Spans nest
    (one thread); a child running past its parent is cut at the parent."""
    out, stack, t = [], [], w0

    def close_until(limit):
        nonlocal t
        while stack and stack[-1][0] <= limit:
            end, name = stack.pop()
            if end > t:
                out.append((t, end, name))
                t = end

    for s, e, n in sorted(spans, key=lambda x: (x[0], -x[1])):
        close_until(s)
        if s > t:
            out.append((t, s, stack[-1][1] if stack else OUTSIDE))
            t = s
        stack.append((min(e, stack[-1][0]) if stack else e, n))
    close_until(w1)
    if w1 > t:
        out.append((t, w1, OUTSIDE))
    return out


def attribution(tr, spans) -> dict:
    """``{innermost span name: device idle ns}`` over the window, averaged
    over devices; the values sum to the window's idle time."""
    w0, w1 = tr.window()
    pieces = innermost(spans, w0, w1)
    acc: dict = {}
    for ops in tr.ops:
        for ns, j in _intersections(idle(ops, w0, w1), pieces):
            name = pieces[j][2]
            acc[name] = acc.get(name, 0) + ns / len(tr.ops)
    return acc


def gaps(tr, spans, top: int = TOP) -> list:
    """``[[seconds, span chain]]``: the longest idle gaps of the first
    device, each with the program spans open at its middle, outermost
    first."""
    w0, w1 = tr.window()
    ranked = sorted(idle(tr.ops[0], w0, w1), key=lambda g: g[0] - g[1])
    out = []
    for a, b in ranked[:top]:
        mid = (a + b) / 2
        chain = [n for s, e, n in sorted(spans, key=lambda x: (x[0], -x[1]))
                 if s <= mid <= e]
        out.append([(b - a) / 1e9, " > ".join(chain) or OUTSIDE])
    return out


def report(path: str) -> dict:
    tr = xplane.load(path)
    rec = {"trace": tr}
    spans = program_spans(rec, path)
    w0, w1 = tr.window()
    idle_ns = (w1 - w0) - tr.busy_ns()
    out = {"trace": path, "window_s": (w1 - w0) / 1e9,
           "idle_ms": idle_ns / 1e6}
    if spans is None:
        out["program_spans"] = 0
        return out
    sweeps = max(1, count(spans, "als.sweep"))
    by_span = attribution(tr, spans)
    out.update({
        "program_spans": len(spans), "sweeps": count(spans, "als.sweep"),
        "idle_ms_per_sweep": {n: ns / sweeps / 1e6 for n, ns in sorted(
            by_span.items(), key=lambda kv: -kv[1])},
        "idle_share_inside_program_spans": 1 - by_span.get(OUTSIDE, 0)
        / idle_ns if idle_ns else None,
        "gc_ms_per_sweep": sum(e - s for s, e, n in spans
                               if n == "python.gc") / sweeps / 1e6,
        "longest_gaps": gaps(tr, spans),
    })
    return out


if __name__ == "__main__":
    trace = sys.argv[1] if len(sys.argv) > 1 else newest_trace()
    if not trace:
        sys.exit(f"no .xplane.pb under {TRACES}")
    print(json.dumps(report(trace), indent=1))
