"""Observability: spans, histograms, bandwidth ledger, roofline, SLOs.

The cross-cutting layer every perf claim in this repo is measured
through.  Six small modules, zero hard dependencies beyond the stdlib:

    trace    context-manager spans + already-measured events into a
             thread-safe bounded ring buffer (recording gated on one
             module-level flag); every span is also a JAX profiler
             annotation, on the device trace's clock; collector pauses
             and backend compiles reported beside them
    hist     fixed log2-bucket histograms (dispatch latency, H2D chunk
             time, disk read time, queue wait, per-launch nnz) threaded
             through ``EngineStats`` / ``JobMetrics`` / ``ServiceMetrics``;
             scheduler latencies additionally keyed per tenant
    ledger   memory-hierarchy bandwidth accounting: (bytes, seconds,
             ops, flops) per tier edge (disk->host, host->device,
             device HBM), per regime, and per (tenant, job); exact
             conservation against ``EngineStats`` by construction
    roofline achieved GB/s per edge + arithmetic intensity and
             memory/compute-bound classification per regime, from the
             ledger (``GetRoofline``, BENCH_7, ``scripts/obs_report.py``)
    slo      per-tenant latency objectives + burn rates over the
             scheduler hists, and the background ``TelemetryExporter``
             (JSONL / Prometheus-textfile push at an interval)
    export   Chrome trace-event JSON (one track per pipeline stage —
             load it in Perfetto to *see* H2D/compute overlap) and
             Prometheus text exposition (``render_prometheus``)

Quick use::

    from repro import obs
    obs.enable()                       # or: with obs.trace.enabled(): ...
    obs.ledger.enable()
    ... run a plan / service ...
    obs.write_chrome_trace("trace.json")
    print(obs.render_prometheus(service.metrics))
    report = obs.roofline_report()     # achieved GB/s per tier edge
"""
from . import ledger, roofline, slo, trace
from .export import (chrome_trace, render_prometheus, track_totals,
                     write_chrome_trace)
from .hist import EngineHists, Hist, ServiceHists, TenantHists
from .ledger import (DEVICE_HBM, DISK_HOST, EDGES, HOST_DEVICE, LEDGER,
                     hbm_model_bytes, job_scope, mttkrp_flops,
                     verify_conservation)
from .roofline import roofline_report
from .slo import DEFAULT_SLOS, SLO, TelemetryExporter, slo_report
from .trace import (TRACING, add_event, clear, compile_count, disable,
                    drain, enable, is_enabled, span, spans, timed)

__all__ = [
    "trace", "TRACING", "span", "timed", "add_event", "enable", "disable",
    "is_enabled", "clear", "spans", "drain", "compile_count",
    "Hist", "EngineHists", "ServiceHists", "TenantHists",
    "chrome_trace", "write_chrome_trace", "track_totals",
    "render_prometheus",
    "ledger", "LEDGER", "EDGES", "DISK_HOST", "HOST_DEVICE", "DEVICE_HBM",
    "job_scope", "hbm_model_bytes", "mttkrp_flops", "verify_conservation",
    "roofline", "roofline_report",
    "slo", "SLO", "DEFAULT_SLOS", "slo_report", "TelemetryExporter",
]
