"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

The trace holds, on one clock, the device planes (``/device:TPU:<n>``,
with a line of XLA module executions and a line of XLA ops) and the host
plane, where the benchmark's ``jax.profiler.TraceAnnotation`` spans
(``bench.window``, ``bench.sweep``, ``bench.mttkrp.mode<n>``,
``bench.update``, ``bench.update_fit``) sit on the Python thread's line.

* busy time: the union of the op intervals of each device inside the
  window, averaged over the devices; idle share is 1 - busy / window;
* module time: summed durations of the executions of one jitted module
  (``jit_stacked_mttkrp``), optionally inside given intervals;
* breakdown: the device ops that took most time, and the longest idle
  gaps, each named by the innermost benchmark span around its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
TOP = 10


@dataclasses.dataclass
class DeviceTrace:
    ops: list          # per device: sorted [(start_ns, end_ns, name)]
    modules: list      # per device: sorted [(start_ns, end_ns, name)]
    spans: list        # host benchmark spans: [(start_ns, end_ns, name)]

    @property
    def devices(self) -> int:
        return len(self.ops)

    def span_intervals(self, name: str) -> list:
        return [(s, e) for s, e, n in self.spans if n == name]

    def window(self) -> tuple:
        """The ``bench.window`` span; the extent of the spans without one."""
        w = self.span_intervals("bench.window")
        if w:
            return w[0]
        if not self.spans:
            return (0, 0)
        return (min(s for s, _, _ in self.spans),
                max(e for _, e, _ in self.spans))

    def busy_ns(self, window=None) -> float:
        """Union of op intervals inside ``window``, averaged over devices."""
        if not self.ops:
            return 0.0
        w0, w1 = window or self.window()
        total = 0
        for ops in self.ops:
            total += _union_length([(max(s, w0), min(e, w1))
                                    for s, e, _ in ops if e > w0 and s < w1])
        return total / len(self.ops)

    def module_ns(self, prefix: str, intervals=None) -> float:
        """Summed durations of ``prefix*`` module executions (inside any of
        ``intervals`` when given), averaged over devices."""
        if not self.modules:
            return 0.0
        total = 0
        for mods in self.modules:
            for s, e, n in mods:
                if not n.startswith(prefix):
                    continue
                if intervals is None:
                    total += e - s
                else:
                    total += sum(max(0, min(e, b) - max(s, a))
                                 for a, b in intervals)
        return total / len(self.modules)

    def top_ops(self, window=None, top: int = TOP) -> list:
        """``[[op name, seconds]]`` of the ops that took most device time."""
        w0, w1 = window or self.window()
        acc: dict = {}
        for ops in self.ops:
            for s, e, n in ops:
                if e > w0 and s < w1:
                    acc[n] = acc.get(n, 0) + (min(e, w1) - max(s, w0))
        k = max(1, len(self.ops))
        ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
        return [[n, t / k / 1e9] for n, t in ranked]

    def idle_gaps(self, window=None, top: int = TOP) -> list:
        """``[[host span, seconds]]``: the longest idle gaps of the first
        device, each named by the innermost benchmark span at its middle."""
        if not self.ops:
            return []
        w0, w1 = window or self.window()
        merged = _merge([(max(s, w0), min(e, w1)) for s, e, _ in self.ops[0]
                         if e > w0 and s < w1])
        gaps, t = [], w0
        for s, e in merged:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if w1 > t:
            gaps.append((t, w1))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.host_span_at((a + b) / 2), (b - a) / 1e9]
                for a, b in gaps[:top]]

    def host_span_at(self, t: float) -> str:
        best = None
        for s, e, n in self.spans:
            if s <= t <= e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, n)
        return best[2] if best else "outside"


def _merge(intervals) -> list:
    out: list = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _union_length(intervals) -> int:
    return sum(e - s for s, e in _merge(intervals))


def find_xplane(trace_dir: str) -> str | None:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def load(path: str) -> DeviceTrace:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    ops, modules, spans = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name \
                and "SparseCore" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines and MODULES_LINE not in lines:
                continue
            mods = _events(lines.get(MODULES_LINE))
            ops.append(_name_ops(_events(lines.get(OPS_LINE)), mods))
            modules.append(mods)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(e for e in _events(line)
                             if e[2].startswith(SPAN_PREFIX))
    return DeviceTrace(ops=ops, modules=modules, spans=sorted(spans))


def short_name(hlo: str) -> str:
    """``%fusion.23 = f32[..] fusion(..)`` -> ``fusion.23``;
    ``jit_stacked_mttkrp(7182)`` -> ``jit_stacked_mttkrp``."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    return name.split("(", 1)[0] if name.endswith(")") else name


def _name_ops(ops: list, modules: list) -> list:
    """Ops named ``<module>/<op>`` after the module execution around them."""
    out, k = [], 0
    for s, e, n in ops:
        while k < len(modules) and modules[k][1] < s:
            k += 1
        inside = k < len(modules) and modules[k][0] <= s
        out.append((s, e, (short_name(modules[k][2]) + "/" if inside else "")
                    + short_name(n)))
    return out


def _events(line) -> list:
    if line is None:
        return []
    return sorted((int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                  for e in line.events)
