"""Schema stability for the metrics snapshots.

External consumers (the Prometheus renderer, dashboards, the regression
gate) key off snapshot dictionaries.  These tests pin a golden key list
per snapshot: keys may be *added* in later PRs (append to the golden
list), but removing or renaming one fails here first, on purpose.
Every snapshot must also round-trip through ``json.dumps`` — no Inf/NaN,
no non-string dict keys, no dataclasses leaking through.
"""
import json

import pytest

from repro.core.streaming import EngineStats
from repro.obs.hist import Hist
from repro.service import JobMetrics, ServiceMetrics

# ----------------------------------------------------------------- goldens
# Grow-only: append new keys at the end; never delete or rename.
JOB_KEYS = {
    "iterations", "queue_wait_s", "run_time_s", "cache_hit", "backend",
    "released_bytes", "h2d_bytes", "disk_bytes", "mttkrp_calls", "launches",
    "put_time_s", "disk_time_s", "dispatch_time_s", "device_time_s",
    "hist",
    "retries", "giveups", "demotions",
    "compiles",
}

SERVICE_KEYS = {
    "jobs_submitted", "jobs_admitted", "jobs_completed", "jobs_failed",
    "jobs_cancelled", "preemptions", "cancel_freed_bytes_total",
    "blco_cache_hits", "blco_cache_misses", "blco_disk_hits",
    "spills", "spill_bytes_total", "loads", "jobs_restored",
    "iterations_total", "iterations_per_sec",
    "h2d_bytes_total", "disk_bytes_total", "disk_time_s_total",
    "launches_total",
    "busy_time_s", "uptime_s",
    "queue_depth", "running_jobs", "host_budget_used_bytes",
    "tenant_iterations", "tenant_shares",
    "admitted_reservation_bytes", "peak_admitted_reservation_bytes",
    "hist",
    "store_rebuilds", "retries_total", "giveups_total", "demotions_total",
    "watchdog_restarts",
    "tenant_hist",
}

ENGINE_STATS_KEYS = {
    "backend", "mttkrp_calls", "h2d_bytes", "disk_bytes", "launches",
    "put_time_s", "disk_time_s", "dispatch_time_s", "device_time_s",
    "total_time_s", "hist",
    "retries", "giveups", "demotions",
    "compiles",
}

HIST_KEYS = {"count", "sum", "min", "max", "buckets"}

ANALYSIS_KEYS = {
    "hot_paths_traced", "jaxpr_eqns_walked", "encodings_verified",
    "launches_analyzed",
    "findings_total", "findings_jaxpr_audit", "findings_cache_churn",
    "findings_encoding", "findings_conflicts",
    "runtime_jaxpr_audit_s", "runtime_cache_churn_s", "runtime_encoding_s",
    "runtime_conflicts_s", "runtime_total_s",
}

ENGINE_HIST_NAMES = {"dispatch_s", "put_chunk_s", "disk_read_s",
                     "launch_nnz"}
SERVICE_HIST_NAMES = ENGINE_HIST_NAMES | {"queue_wait_s", "quantum_s"}

TENANT_HIST_NAMES = {"queue_wait_s", "quantum_s"}

#: Prometheus series the dashboards scrape from ``render_prometheus``:
#: grow-only, same contract as the snapshot keys above.
PROM_SERIES = {
    "repro_trace_dropped_spans_total", "repro_trace_enabled",
    "repro_trace_buffered_spans", "repro_trace_capacity_spans",
    "repro_ledger_enabled", "repro_ledger_bytes_total",
    "repro_ledger_seconds_total", "repro_ledger_ops_total",
    "repro_ledger_gb_per_s",
}

LEDGER_EDGE_KEYS = {"bytes", "seconds", "ops", "flops", "gb_per_s"}


def test_job_metrics_snapshot_keys_only_grow():
    snap = JobMetrics().snapshot()
    missing = JOB_KEYS - set(snap)
    assert not missing, f"JobMetrics.snapshot() lost keys: {missing}"
    json.dumps(snap)
    assert set(snap["hist"]) >= ENGINE_HIST_NAMES
    for h in snap["hist"].values():
        assert set(h) >= HIST_KEYS


def test_service_metrics_snapshot_keys_only_grow():
    snap = ServiceMetrics().snapshot()
    missing = SERVICE_KEYS - set(snap)
    assert not missing, f"ServiceMetrics.snapshot() lost keys: {missing}"
    json.dumps(snap)
    assert set(snap["hist"]) >= SERVICE_HIST_NAMES
    for h in snap["hist"].values():
        assert set(h) >= HIST_KEYS


def test_engine_stats_snapshot_keys_only_grow():
    snap = EngineStats().snapshot()
    missing = ENGINE_STATS_KEYS - set(snap)
    assert not missing, f"EngineStats.snapshot() lost keys: {missing}"
    json.dumps(snap)


def test_snapshots_json_safe_with_data():
    m = ServiceMetrics()
    m.record_iteration("alice")
    m.record_iteration("bob")
    m.hist.queue_wait_s.record(0.01)
    m.hist.quantum_s.record(0.5)
    m.busy_time_s = 0.5
    text = json.dumps(m.snapshot())
    back = json.loads(text)
    assert back["tenant_iterations"] == {"alice": 1, "bob": 1}
    assert back["tenant_shares"]["alice"] == pytest.approx(0.5)
    # bucket keys are string-typed les, safe as JSON object keys
    assert all(isinstance(k, str)
               for k in back["hist"]["quantum_s"]["buckets"])


def test_tenant_hist_snapshot_shape():
    m = ServiceMetrics()
    m.hist.record_queue_wait("acme", 0.01)
    m.hist.record_quantum("acme", 0.5)
    snap = m.snapshot()
    assert set(snap["tenant_hist"]) == {"acme"}
    per = snap["tenant_hist"]["acme"]
    assert set(per) == TENANT_HIST_NAMES
    for h in per.values():
        assert set(h) >= HIST_KEYS
    json.dumps(snap)


def test_prometheus_series_only_grow():
    """Every golden Prometheus series renders (trace + ledger state and
    the labelled per-tenant histograms), with the ledger series labelled
    per edge."""
    from repro.obs import ledger
    from repro.obs.export import render_prometheus
    m = ServiceMetrics()
    m.hist.record_queue_wait("acme", 0.01)
    ledger.clear()
    ledger.enable()
    try:
        ledger.record(ledger.HOST_DEVICE, 1024, 0.5, regime="streamed")
        text = render_prometheus(m)
    finally:
        ledger.disable()
        ledger.clear()
    for series in PROM_SERIES:
        assert f"\n{series}" in text or text.startswith(series), \
            f"missing Prometheus series {series}"
    assert 'repro_ledger_bytes_total{edge="host_device"} 1024' in text
    assert 'repro_tenant_queue_wait_s_count{tenant="acme"} 1' in text


def test_ledger_snapshot_edge_keys_only_grow():
    from repro.obs import ledger
    ledger.clear()
    ledger.enable()
    try:
        ledger.record(ledger.DISK_HOST, 10, 0.1, regime="disk_streamed")
        snap = ledger.snapshot()
    finally:
        ledger.disable()
        ledger.clear()
    acct = snap["edges"]["disk_host"]
    missing = LEDGER_EDGE_KEYS - set(acct)
    assert not missing, f"ledger edge account lost keys: {missing}"
    json.dumps(snap)


def test_trace_verify_metrics_snapshot_keys_only_grow():
    from repro.analysis.trace.metrics import TraceVerifyMetrics
    snap = TraceVerifyMetrics().snapshot()
    missing = ANALYSIS_KEYS - set(snap)
    assert not missing, f"TraceVerifyMetrics.snapshot() lost keys: {missing}"
    json.dumps(snap)


def test_trace_verify_prometheus_render():
    """Every analysis golden key appears as a repro_analysis_* sample."""
    from repro.analysis.trace.metrics import TraceVerifyMetrics
    from repro.obs.export import render_prometheus_analysis

    class _F:                         # a Finding-shaped stub
        pass_id = "trace-encoding"

    m = TraceVerifyMetrics(hot_paths_traced=6, runtime_total_s=0.5)
    m.count_findings([_F(), _F()])
    text = render_prometheus_analysis(m)
    for key in ANALYSIS_KEYS:
        assert f"repro_analysis_{key} " in text
    assert "repro_analysis_findings_total 2" in text
    assert "repro_analysis_findings_encoding 2" in text
    assert "repro_analysis_hot_paths_traced 6" in text


def test_hist_snapshot_has_no_infinities():
    h = Hist()
    h.record(1e12)                       # lands in the +Inf bucket
    snap = h.snapshot()
    text = json.dumps(snap, allow_nan=False)   # raises on Inf/NaN
    assert "+Inf" in snap["buckets"]
    assert json.loads(text)["count"] == 1


def test_iterations_per_sec_uses_busy_time_not_wall_clock():
    m = ServiceMetrics()
    m.iterations_total = 10
    m.busy_time_s = 2.0
    assert m.iterations_per_sec() == pytest.approx(5.0)
    # idle time does not decay the rate: back-date construction far into
    # the past — a wall-clock denominator would crater the value
    m.started_s -= 3600.0
    assert m.iterations_per_sec() == pytest.approx(5.0)
    assert m.uptime_s >= 3600.0
    # and with no busy time, the rate is 0, not a division error
    assert ServiceMetrics().iterations_per_sec() == 0.0
