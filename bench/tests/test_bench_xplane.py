"""Trace reduction: on hand-made intervals, and on a trace recorded on a
TPU v5e (``bench/testdata/chicago_tiny.xplane.pb``: one chicago sweep at
20,000 nnz, recorded by ``run_cell`` with the benchmark's profiler
options)."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import run, xplane

RECORDED = Path(__file__).resolve().parents[1] / "testdata" / \
    "chicago_tiny.xplane.pb"
MS = 10 ** 6


def synthetic():
    mods = [(10 * MS, 40 * MS, "jit_stacked_mttkrp(1)"),
            (45 * MS, 47 * MS, "jit_matmul(2)"),
            (60 * MS, 90 * MS, "jit_stacked_mttkrp(1)")]
    ops = [(10 * MS, 30 * MS, "jit_stacked_mttkrp/while.1"),
           (20 * MS, 40 * MS, "jit_stacked_mttkrp/fusion.2"),
           (45 * MS, 47 * MS, "jit_matmul/dot.1"),
           (60 * MS, 90 * MS, "jit_stacked_mttkrp/while.1")]
    spans = [(0, 100 * MS, "bench.window"),
             (0, 50 * MS, "bench.sweep"), (50 * MS, 100 * MS, "bench.sweep"),
             (5 * MS, 41 * MS, "bench.mttkrp.mode0"),
             (41 * MS, 58 * MS, "bench.update_fit")]
    return xplane.DeviceTrace(ops=[ops], modules=[mods], spans=sorted(spans))


def test_busy_and_idle():
    tr = synthetic()
    assert tr.window() == (0, 100 * MS)
    assert tr.busy_ns() == 30 * MS + 2 * MS + 30 * MS     # nested ops once
    rec = {"trace": tr}
    assert run.metric_reader("device.idle_share")(rec) == pytest.approx(38.0)


def test_module_time_inside_intervals():
    tr = synthetic()
    assert tr.module_ns("jit_stacked_mttkrp") == 60 * MS
    assert tr.module_ns("jit_stacked_mttkrp", [(0, 50 * MS)]) == 30 * MS
    rec = {"trace": tr, "call_modes": [0, 1]}
    assert run.metric_reader("mttkrp.device_ms")(rec) == pytest.approx(30.0)
    # each sweep's wall less its MTTKRP device time: (50-30 + 50-30) / 2
    assert run.metric_reader("als.outside_mttkrp_ms")(rec) == \
        pytest.approx(20.0)


def test_breakdown():
    tr = synthetic()
    top = tr.top_ops()
    assert top[0] == ["jit_stacked_mttkrp/while.1", pytest.approx(0.05)]
    gaps = tr.idle_gaps()
    assert gaps[0] == ["bench.update_fit", pytest.approx(0.013)]  # 47-60 ms
    assert ["bench.update_fit", pytest.approx(0.005)] in gaps    # 40-45 ms
    assert ["bench.mttkrp.mode0", pytest.approx(0.010)] in gaps  # 0-10 ms
    assert ["bench.sweep", pytest.approx(0.010)] in gaps         # 90-100 ms
    assert sum(g[1] for g in gaps) == pytest.approx(0.038)


def test_readers_return_nothing_without_a_trace():
    rec = {"trace": None, "call_modes": [0], "sweeps": 1, "stats": {}}
    for name in ("device.idle_share", "mttkrp.device_ms", "mttkrp_roofline",
                 "als.outside_mttkrp_ms"):
        assert run.metric_reader(name)(rec) is None


def test_short_names():
    assert xplane.short_name(
        "%fusion.23 = f32[2097152,32]{0,1:T(8,128)} fusion(f32[2] %a), "
        "kind=kCustom") == "fusion.23"
    assert xplane.short_name("jit_stacked_mttkrp(7182459956583007254)") == \
        "jit_stacked_mttkrp"


def test_recorded_trace():
    tr = xplane.load(str(RECORDED))
    assert tr.devices == 1
    w0, w1 = tr.window()
    assert w1 > w0
    busy = tr.busy_ns()
    assert 0 < busy <= w1 - w0
    assert tr.module_ns("jit_stacked_mttkrp", [(w0, w1)]) > 0
    names = {n for _, _, n in tr.spans}
    assert {"bench.window", "bench.sweep", "bench.update_fit"} <= names
    assert {f"bench.mttkrp.mode{m}" for m in range(4)} <= names
    top = tr.top_ops()
    assert 0 < len(top) <= xplane.TOP
    assert all(t > 0 and "/" in n for n, t in top)
    gaps = tr.idle_gaps()
    assert gaps and all(n.startswith("bench.") and t > 0 for n, t in gaps)
    rec = {"trace": tr, "call_modes": [0, 1, 2, 3], "dims": (6186, 24, 77, 32),
           "nnz": 20_000, "rank": 32, "value_dtype": "float32",
           "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12},
           "chips": 1}
    share = run.metric_reader("mttkrp_roofline")(rec)
    assert 0 < share < 100
    assert 0 < run.metric_reader("device.idle_share")(rec) < 100
