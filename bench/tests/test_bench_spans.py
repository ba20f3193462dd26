"""The program's spans in a trace: the idle-inside-span arithmetic on
hand-made intervals, and the readers on traces recorded on a TPU v5e
(``bench/testdata/chicago_tiny_spans.xplane.pb``: chicago at 20,000 nnz,
recorded by ``run_cell`` with the benchmark's profiler options and a
program that annotates its spans; ``chicago_tiny.xplane.pb``: the same
before it did)."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import run, spans, xplane

TESTDATA = Path(__file__).resolve().parents[1] / "testdata"
WITH_SPANS = TESTDATA / "chicago_tiny_spans.xplane.pb"
WITHOUT_SPANS = TESTDATA / "chicago_tiny.xplane.pb"
IDLE_READERS = ("mttkrp.issue_idle_ms", "mttkrp.fence_idle_ms",
                "als.update_idle_ms", "als.fit_idle_ms")
READERS = IDLE_READERS + ("host.gc_ms",)
MS = 10 ** 6


def synthetic():
    """One sweep in a 100-ms window.  Device ops: the scan at 8-44 ms,
    update ops at 46-48 and 55-58, a fit op at 62-64; nothing after."""
    program = [(2, 98, "als.sweep"), (5, 45, "als.mttkrp"),
               (5, 45, "plan.mttkrp"), (5, 10, "plan.issue"),
               (6, 10, "launch_cache.scan"), (10, 45, "plan.fence"),
               (45, 60, "als.update"), (50, 53, "python.gc"),
               (60, 95, "als.fit")]
    ops = [(8, 44, "jit_stacked_mttkrp/while.1"), (46, 48, "jit_dot/dot"),
           (55, 58, "jit__pinv/x"), (62, 64, "jit_norm/y")]
    tr = xplane.DeviceTrace(
        ops=[[(s * MS, e * MS, n) for s, e, n in ops]],
        modules=[[]], spans=[(0, 100 * MS, "bench.window")])
    return tr, sorted((s * MS, e * MS, n) for s, e, n in program)


def test_idle_inside_nested_spans():
    tr, sp = synthetic()
    # 5-8 ms before the scan starts; the child span's 6-8 ms counts too
    assert spans.idle_inside(tr, sp, "plan.issue") == 3 * MS
    assert spans.idle_inside(tr, sp, "launch_cache.scan") == 2 * MS
    assert spans.idle_inside(tr, sp, "als.mttkrp") == 4 * MS   # + 44-45
    assert spans.idle_inside(tr, sp, "als.sweep") == 53 * MS


def test_span_with_no_ops_is_idle_throughout():
    tr, sp = synthetic()
    # the fit's op covers 62-64 of its 60-95 ms
    assert spans.idle_inside(tr, sp, "als.fit") == 33 * MS
    assert spans.idle_inside(tr, sp, "python.gc") == 3 * MS


def test_gap_straddling_a_span_edge_is_split():
    tr, sp = synthetic()
    # the 44-46 ms gap: 1 ms in plan.fence, 1 ms in als.update
    assert spans.idle_inside(tr, sp, "plan.fence") == 1 * MS
    # 45-46, 48-55 and 58-60 ms
    assert spans.idle_inside(tr, sp, "als.update") == 10 * MS


def test_innermost_attribution_sums_to_the_window_idle():
    tr, sp = synthetic()
    by = spans.attribution(tr, sp)
    assert by == {spans.OUTSIDE: 4 * MS, "als.sweep": 6 * MS,
                  "plan.issue": 1 * MS, "launch_cache.scan": 2 * MS,
                  "plan.fence": 1 * MS, "als.update": 7 * MS,
                  "python.gc": 3 * MS, "als.fit": 33 * MS}
    w0, w1 = tr.window()
    assert sum(by.values()) == (w1 - w0) - tr.busy_ns()
    pieces = spans.innermost(sp, w0, w1)
    assert pieces[0] == (0, 2 * MS, spans.OUTSIDE)
    assert all(a[1] == b[0] for a, b in zip(pieces, pieces[1:]))
    longest = spans.gaps(tr, sp, top=1)
    assert longest == [[0.036, "als.sweep > als.fit"]]     # 64-100 ms


def test_readers_on_synthetic_spans(monkeypatch):
    tr, sp = synthetic()
    monkeypatch.setattr(spans, "program_spans", lambda rec: sp)
    rec = {"trace": tr}
    got = {n: run.metric_reader(n)(rec) for n in READERS}
    assert got == {"mttkrp.issue_idle_ms": 3.0, "mttkrp.fence_idle_ms": 1.0,
                   "als.update_idle_ms": 10.0, "als.fit_idle_ms": 33.0,
                   "host.gc_ms": 3.0}


def read_all(path, monkeypatch):
    monkeypatch.setattr(spans, "newest_trace", lambda: str(path))
    rec = {"trace": xplane.load(str(path))}
    return {n: run.metric_reader(n)(rec) for n in READERS}


def test_readers_on_a_recorded_trace_with_program_spans(monkeypatch):
    got = read_all(WITH_SPANS, monkeypatch)
    for name in IDLE_READERS:
        assert got[name] > 0, name
    tr = xplane.load(str(WITH_SPANS))
    sp = spans.program_spans({"trace": tr}, str(WITH_SPANS))
    if spans.count(sp, "python.gc"):
        assert got["host.gc_ms"] > 0
    else:
        assert got["host.gc_ms"] == 0.0
    w0, w1 = tr.window()
    by = spans.attribution(tr, sp)
    assert sum(by.values()) == pytest.approx((w1 - w0) - tr.busy_ns())
    report = spans.report(str(WITH_SPANS))
    assert report["sweeps"] == spans.count(sp, "als.sweep") >= 1
    assert 0 < report["idle_share_inside_program_spans"] <= 1


def test_readers_on_a_recorded_trace_without_program_spans(monkeypatch):
    assert read_all(WITHOUT_SPANS, monkeypatch) == dict.fromkeys(READERS)


def test_readers_refuse_a_trace_of_another_window(monkeypatch):
    # the newest trace holds program spans, but not the record's window
    monkeypatch.setattr(spans, "newest_trace", lambda: str(WITH_SPANS))
    rec = {"trace": xplane.load(str(WITHOUT_SPANS))}
    assert rec["trace"].window() != xplane.load(str(WITH_SPANS)).window()
    assert spans.program_spans(rec) is None
    assert {n: run.metric_reader(n)(rec) for n in READERS} == \
        dict.fromkeys(READERS)


def test_readers_without_a_trace():
    rec = {"trace": None}
    assert {n: run.metric_reader(n)(rec) for n in READERS} == \
        dict.fromkeys(READERS)
