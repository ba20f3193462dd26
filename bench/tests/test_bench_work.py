"""Compulsory work, peaks, and the harness's refusals (CPU, seconds)."""
from __future__ import annotations

import inspect
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import run, work

ROOT = Path(__file__).resolve().parents[2]
NELL2 = (12092, 9184, 28818)
PEAKS = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}


def test_nell2_work_by_hand():
    # 14 + 14 + 15 = 43 coordinate bits -> 6 bytes, plus a 4-byte value
    assert work.coord_bytes(NELL2) == 6
    nnz, rank = 25_000_000, 32
    for mode, others in ((0, 9184 + 28818), (1, 12092 + 28818),
                         (2, 12092 + 9184)):
        want = nnz * 10 + 4 * 32 * others + 4 * 32 * NELL2[mode]
        assert work.mttkrp_bytes(NELL2, nnz, rank, np.float32, mode) == want
        assert work.mttkrp_flops(NELL2, nnz, rank, np.float32, mode) \
            == nnz * 32 * 3
    # the bytes side binds: about 10 flop/B against a ridge near 240
    least = work.least_seconds(NELL2, nnz, rank, np.float32, 0, PEAKS)
    assert least == pytest.approx((250_000_000 + 128 * 50094) / 819e9)


def test_chicago_work_by_hand():
    dims = (6186, 24, 77, 32)      # 13 + 5 + 7 + 5 = 30 bits -> 4 bytes
    assert work.coord_bytes(dims) == 4
    assert work.mttkrp_bytes(dims, 5_330_673, 32, np.float32, 1) == \
        5_330_673 * 8 + 128 * (6186 + 77 + 32) + 128 * 24
    assert work.mttkrp_bytes(dims, 10, 32, np.float64, 1) - \
        work.mttkrp_bytes(dims, 10, 32, np.float32, 1) == 10 * 4


def test_work_reads_only_the_tensor_and_the_call():
    # no kernel, resolution or regime can enter the count
    for fn in (work.mttkrp_bytes, work.mttkrp_flops):
        assert list(inspect.signature(fn).parameters) == \
            ["dims", "nnz", "rank", "value_dtype", "mode"]


def test_roofline_reader():
    class Trace:
        def window(self):
            return (0, 10 ** 9)

        def module_ns(self, prefix, intervals=None):
            return 5 * 10 ** 8 if prefix == "jit_stacked_mttkrp" else 0

    rec = {"trace": Trace(), "call_modes": [0, 1, 2], "dims": NELL2,
           "nnz": 25_000_000, "rank": 32, "value_dtype": "float32",
           "peaks": PEAKS}
    least = sum(work.least_seconds(NELL2, 25_000_000, 32, "float32", m, PEAKS)
                for m in range(3))
    assert run.metric_reader("mttkrp_roofline")(rec) == \
        pytest.approx(100 * least / 0.5)


def test_peaks_table():
    table = json.loads(work.PEAKS.read_text())
    v5e = work.peaks_for("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["flops_per_s"] == 197e12
    assert all("source" in p for p in table.values())
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks_for("TPU v99")


def test_unknown_device_kind_exits_nonzero(monkeypatch):
    class Dev:
        platform = "tpu"
        device_kind = "TPU v99"

    import jax
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    assert run.main(["--workload", "chicago.in_memory", "--seed", "1",
                     "--seconds", "1"]) == run.EXIT_UNKNOWN_DEVICE


def test_cpu_backend_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "chicago.in_memory",
         "--seed", str(2 ** 33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == run.EXIT_NO_CHIP
    assert "needs 1 TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_benchmark_json_names_files_that_exist():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
    for w in bench["workloads"]:
        cell = run.load_cell(w["name"])
        assert set(cell["limits"]) == {"mttkrp_err", "update_err", "fit_err"}
        for m in cell["per_layer"]:
            assert callable(run.metric_reader(m["name"]))
