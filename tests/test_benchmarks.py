"""Benchmark harness regressions: the _time warmup=0 fix and the
machine-readable BENCH_3.json dispatch bench."""
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import run as bench                                    # noqa: E402


def test_time_with_zero_warmup():
    """Satellite: _time(warmup=0) used to crash with NameError (r unbound)."""
    calls = []
    t = bench._time(lambda: calls.append(1), warmup=0, iters=3)
    assert t >= 0 and len(calls) == 3
    # still correct with warmup and a device-array result
    import jax.numpy as jnp
    t = bench._time(lambda: jnp.arange(8) * 2, warmup=1, iters=2)
    assert t >= 0


def test_bench_dispatch_json_schema(tmp_path, monkeypatch):
    """Fast-mode dispatch bench emits the machine-readable trajectory file
    with one-dispatch cached paths and the loop's per-launch dispatches."""
    monkeypatch.setattr(bench, "SUITE", ["uber-like"])
    path = tmp_path / "BENCH_3.json"
    rows = []
    payload = bench.bench_dispatch(rows, fast=True, json_path=str(path))
    on_disk = json.loads(path.read_text())
    assert on_disk == payload
    assert payload["bench"] == "fused_single_dispatch_blco_mttkrp"
    assert payload["geomean_speedup_cached_scan_vs_per_launch_loop"] > 0
    s = payload["suites"]["uber-like"]
    for key in ("per_launch_loop_us", "cached_scan_xla_us",
                "fused_pallas_us", "phases_pallas_us", "launches"):
        assert s[key] > 0, key
    assert s["dispatches_per_call_cached"] == 1
    assert s["dispatches_per_call_loop"] == s["launches"] > 1
    assert any(name.startswith("bench3.") for name, _, _ in rows)


def test_bench_multitenant_json_schema(tmp_path):
    """The weighted multi-tenant bench emits per-tenant iterations/sec and
    shares within 10% of the weights (the ISSUE 4 acceptance), plus the
    measured bytes freed by a mid-run cancellation."""
    path = tmp_path / "BENCH_4.json"
    rows = []
    payload = bench.bench_multitenant(rows, fast=True, json_path=str(path))
    on_disk = json.loads(path.read_text())
    assert on_disk == payload
    assert payload["bench"] == "weighted_multi_tenant_service"
    assert payload["max_share_deviation_vs_weights"] <= 0.1
    assert payload["cancelled_jobs"] == 1
    assert payload["cancel_freed_bytes"] > 0
    tenants = payload["tenants"]
    assert abs(sum(t["expected_share"] for t in tenants.values()) - 1) < 1e-9
    for name, t in tenants.items():
        assert t["iterations"] > 0 and t["iters_per_sec"] > 0, name
        assert abs(t["share"] - t["expected_share"]) <= \
            0.1 * t["expected_share"], name
    heavy, light = tenants["heavy"], tenants["light-1"]
    assert heavy["weight"] == 2 * light["weight"]
    assert heavy["iterations"] == 2 * light["iterations"]
    assert any(name.startswith("service4.") for name, _, _ in rows)


def test_bench_oom_json_schema(tmp_path):
    """The memory-hierarchy bench writes its store to the given dir and
    emits per-tier timings (outputs equal to f32 rounding) + the
    bounded-window ratio."""
    path = tmp_path / "BENCH_5.json"
    store = tmp_path / "store"
    store.mkdir()
    rows = []
    payload = bench.bench_oom(rows, fast=True, json_path=str(path),
                              store_dir=str(store))
    on_disk = json.loads(path.read_text())
    assert on_disk == payload
    assert payload["bench"] == "memory_hierarchy_mttkrp"
    assert (store / "bench_oom.blco").exists()   # smoke-run on a real store
    for tier in ("in_memory", "host_streamed", "disk_streamed"):
        assert payload["us_per_call"][tier] > 0, tier
        assert payload["gb_per_s"][tier] > 0, tier
    assert payload["store_file_bytes"] > 0
    # the lazy/bounded window is strictly smaller than the old eager
    # all-launches-resident footprint (the satellite regression, measured)
    assert payload["host_window_bytes"] \
        < payload["all_launches_padded_bytes"]
    assert 0 < payload["host_window_ratio_vs_all_launches"] < 1
    d = payload["disk_stats"]
    assert d["disk_bytes"] > 0 and d["backend"] == "disk_streamed"
    assert any(name.startswith("bench5.") for name, _, _ in rows)


def test_committed_bench5_memory_hierarchy():
    """The committed memory-hierarchy trajectory must show all three tiers
    measured and a genuinely bounded disk-streaming host window."""
    path = os.path.join(REPO, "BENCH_5.json")
    assert os.path.exists(path), "BENCH_5.json must be committed"
    payload = json.loads(open(path).read())
    for tier in ("in_memory", "host_streamed", "disk_streamed"):
        assert payload["gb_per_s"][tier] > 0, tier
    assert payload["host_window_ratio_vs_all_launches"] < 0.5


def test_bench_roofline_json_schema(tmp_path):
    """The roofline bench measures peaks, attributes per-edge bandwidth
    per regime, and conserves against EngineStats exactly."""
    path = tmp_path / "BENCH_7.json"
    store = tmp_path / "store"
    store.mkdir()
    rows = []
    payload = bench.bench_roofline(rows, fast=True, json_path=str(path),
                                   store_dir=str(store))
    on_disk = json.loads(path.read_text())
    assert on_disk == payload
    assert payload["bench"] == "bandwidth_roofline"
    for edge in ("disk_host", "host_device", "device_hbm"):
        assert payload["peak_gb_per_s"][edge] > 0, edge
    assert payload["max_edge_rel_err"] == 0.0       # the conservation law
    for regime in ("in_memory", "streamed", "disk_streamed"):
        assert payload["saturated_edge"][regime] in (
            "disk_host", "host_device", "device_hbm"), regime
        assert payload["bound"][regime] in (
            "memory_bound", "compute_bound"), regime
        assert payload["us_per_call"][regime] > 0, regime
    assert payload["achieved_fraction"]             # non-empty, all > 0
    assert all(v > 0 for v in payload["achieved_fraction"].values())
    assert any(name.startswith("bench7.") for name, _, _ in rows)


def test_committed_bench7_roofline():
    """The committed roofline trajectory must conserve exactly, name a
    saturated edge for the disk-streamed and host-streamed regimes, and
    hold the tracing+ledger overhead bar on the in-memory path."""
    path = os.path.join(REPO, "BENCH_7.json")
    assert os.path.exists(path), "BENCH_7.json must be committed"
    payload = json.loads(open(path).read())
    assert payload["max_edge_rel_err"] == 0.0
    for regime in ("disk_streamed", "streamed"):
        assert payload["saturated_edge"][regime] in (
            "disk_host", "host_device", "device_hbm"), regime
        assert payload["achieved_fraction"][
            f"{regime}.{payload['saturated_edge'][regime]}"] > 0
    assert payload["obs_enabled_overhead_frac"] < 0.02


def test_committed_bench4_weighted_shares():
    """The committed multi-tenant trajectory must hold the 10% share bound
    and show a real cancellation release."""
    path = os.path.join(REPO, "BENCH_4.json")
    assert os.path.exists(path), "BENCH_4.json must be committed"
    payload = json.loads(open(path).read())
    assert payload["max_share_deviation_vs_weights"] <= 0.1
    assert payload["cancel_freed_bytes"] > 0


def test_committed_bench3_shows_speedup():
    """The committed perf trajectory must show the fused/cached path beating
    the PR-2 per-launch loop (acceptance: >= 2x on this machine)."""
    path = os.path.join(REPO, "BENCH_3.json")
    assert os.path.exists(path), "BENCH_3.json must be committed"
    payload = json.loads(open(path).read())
    assert payload["geomean_speedup_cached_scan_vs_per_launch_loop"] >= 2.0
    for name, s in payload["suites"].items():
        assert s["dispatches_per_call_cached"] == 1, name


@pytest.mark.parametrize("env_set", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, env_set):
    """The entry points' compile cache: JAX_COMPILATION_CACHE_DIR wins and
    nothing is set in code; otherwise one fixed path in the checkout."""
    import jax
    from repro.core.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        if env_set:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = os.path.join(REPO, ".jax_cache")
            assert enable_compile_cache() == want
            assert enable_compile_cache() == want        # fixed, not fresh
            assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
