"""The comparison that decides ``correct``, driven through a whole run.

Each test skips only the harness's look for a chip: ``run_cell`` makes the
cell's tensor (cut to a few thousand nnz, dims kept), builds BLCO, plans it
under the cell's budget rule, warms up, runs the window and compares, on
the CPU.  A sound run is correct under the cell's committed limits; the
control (the reference in bfloat16) and each planted fault are not.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, run

NNZ = 6000
PEAKS = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}
CELLS = ["nell2.in_memory", "chicago.in_memory"]


@pytest.fixture(scope="module")
def clock():
    return run.CompileClock(jax)


@pytest.fixture(autouse=True)
def restore_matmul_precision():
    # run_cell sets the harness's matmul precision process-wide
    before = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", before)


@pytest.fixture(autouse=True)
def small_launches(monkeypatch):
    # several launches, as on the chip, so the scan runs more than one step
    import repro.core.blco as blco
    monkeypatch.setattr(blco, "default_launch_nnz", lambda *a: 1024)


def small_run(name, clock, seed=2 ** 33 + 7, controls=()):
    cell = run.load_cell(name)
    cell["config"] = dict(cell["config"], nnz=NNZ)
    return run.run_cell(cell, seed, 0.2, False, device=jax.devices()[0],
                        bytes_limit=8 << 30, peaks=PEAKS, t_start=0.0,
                        clock=clock, controls=controls)


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct_and_the_control_is_not(name, clock):
    line = small_run(name, clock, controls=("bfloat16",))
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert list(line)[-2:] == ["checks", "control"]
    limits = run.load_cell(name)["limits"]
    ctrl = line["control"]["bfloat16"]
    assert not check.verdict({"numbers": ctrl, "faults": []}, limits), ctrl


def test_result_line_shape(clock):
    line = small_run("chicago.in_memory", clock)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert set(line["metrics"]) == {"sweep_s", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"


def test_step_that_returns_its_state_unchanged(clock, monkeypatch):
    from repro import core
    monkeypatch.setattr(core, "cp_als_step", lambda fn, state: state)
    line = small_run("chicago.in_memory", clock)
    assert not line["correct"] and line["checks"]["faults"]["value"] > 0


def _broken_scan(monkeypatch, breaker):
    import repro.core.launches as launches
    real = launches.stacked_mttkrp

    def broken(hi, lo, vals, bases, factors, **kw):
        return breaker(real, hi, lo, vals, bases, factors, **kw)
    monkeypatch.setattr(launches, "stacked_mttkrp", broken)


@pytest.mark.parametrize("name", CELLS)
def test_half_the_nonzeros_left_out(name, clock, monkeypatch):
    def half(real, hi, lo, vals, bases, factors, **kw):
        keep = (jnp.arange(vals.size) % 2 == 0).reshape(vals.shape)
        return 2 * real(hi, lo, jnp.where(keep, vals, 0), bases, factors, **kw)
    _broken_scan(monkeypatch, half)
    line = small_run(name, clock)
    assert not line["correct"]
    assert line["checks"]["mttkrp_err"]["value"] > \
        line["checks"]["mttkrp_err"]["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_answer_altered_where_it_is_produced(name, clock, monkeypatch):
    def altered(real, *args, **kw):
        out = real(*args, **kw)
        return out.at[0].set(out[1])
    _broken_scan(monkeypatch, altered)
    line = small_run(name, clock)
    assert not line["correct"]


@pytest.mark.parametrize("name", CELLS)
def test_fault_in_an_unsampled_rank_column(name, clock, monkeypatch):
    real_compare = check.compare
    seen = {}

    def compare(*args, **kw):
        out = real_compare(*args, **kw)
        seen["cols"] = out["cols"]
        return out
    monkeypatch.setattr(check, "compare", compare)
    assert small_run(name, clock)["correct"]
    col = next(c for c in range(32) if c not in seen["cols"])

    def one_column_off(real, *args, **kw):
        out = real(*args, **kw)
        return out.at[..., col].multiply(1.01)
    _broken_scan(monkeypatch, one_column_off)
    line = small_run(name, clock)
    assert seen["cols"] == [c for c in seen["cols"] if c != col]
    assert not line["correct"]
    assert line["checks"]["mttkrp_err"]["value"] > \
        line["checks"]["mttkrp_err"]["limit"]


def test_wrong_fit_is_caught(clock, monkeypatch):
    from repro import core
    real = core.cp_als_step

    def step(fn, state):
        real(fn, state)
        state.fits[-1] += 1e-3
        return state
    monkeypatch.setattr(core, "cp_als_step", step)
    line = small_run("chicago.in_memory", clock)
    assert not line["correct"]
    assert line["checks"]["fit_err"]["value"] > \
        line["checks"]["fit_err"]["limit"]


def test_sample_is_drawn_from_the_seed():
    a = check.sample(7, np.random.default_rng(3))
    assert a == check.sample(7, np.random.default_rng(3))
    assert a[-1] == 6 and len(a) == 2
    assert check.sample(1, np.random.default_rng(3)) == [0]
    assert check.sample(0, np.random.default_rng(3)) == []
