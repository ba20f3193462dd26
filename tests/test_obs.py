"""Observability layer: span tracer, log2 histograms, exporters, and the
end-to-end acceptance path (traced disk-streamed CP-ALS whose span sums
agree with the EngineStats the same timestamps fed)."""
import json
import math
import threading
import time

import numpy as np
import pytest

from repro import core, obs
from repro.core.cp_als import cp_als
from repro.engine import plan_for
from repro.obs.hist import Hist, NBUCKETS, bucket_index, bucket_le


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts and ends with the global tracer off, empty, and
    back at the default ring-buffer capacity (enable() keeps the current
    capacity, so a capacity-shrinking test must not leak into the next)."""
    obs.enable(capacity=obs.trace.DEFAULT_CAPACITY)
    obs.disable()
    obs.clear()
    yield
    obs.enable(capacity=obs.trace.DEFAULT_CAPACITY)
    obs.disable()
    obs.clear()


def _factors(dims, rank=4, seed=0):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal((d, rank)).astype(np.float32))
            for d in dims]


# ------------------------------------------------------------------- hist
def test_bucket_index_boundaries():
    # exact powers of two land in the bucket whose le equals them
    for v in (0.25, 0.5, 1.0, 2.0, 1024.0):
        i = bucket_index(v)
        assert bucket_le(i) == v
    # just above a power of two spills into the next bucket
    assert bucket_index(1.0000001) == bucket_index(1.0) + 1
    # non-positive values land in the lowest bucket
    assert bucket_index(0.0) == 0
    assert bucket_index(-3.5) == 0
    # huge values clamp into the final +Inf bucket
    assert bucket_index(2.0 ** 40) == NBUCKETS - 1
    assert bucket_le(NBUCKETS - 1) == math.inf


def test_hist_record_merge_quantile():
    h = Hist()
    for v in (0.001, 0.002, 0.004, 0.008):
        h.record(v)
    assert h.count == 4
    assert h.sum == pytest.approx(0.015)
    assert h.min == 0.001 and h.max == 0.008
    assert h.mean == pytest.approx(0.015 / 4)
    assert h.quantile(1.0) == h.max
    assert h.quantile(0.25) <= h.quantile(0.75)
    other = Hist()
    other.record(1.0)
    h.merge(other)
    assert h.count == 5 and h.max == 1.0
    # cumulative buckets are monotone and end with +Inf at total count
    cum = h.cumulative()
    assert cum[-1][0] == math.inf and cum[-1][1] == 5
    assert all(c1 <= c2 for (_, c1), (_, c2) in zip(cum, cum[1:]))


def test_hist_snapshot_json_safe_when_empty():
    snap = Hist().snapshot()
    json.dumps(snap)                         # inf min/max would blow up here
    assert snap["count"] == 0 and snap["buckets"] == {}
    h = Hist()
    h.record(3.0)
    snap = h.snapshot()
    json.dumps(snap)
    assert sum(snap["buckets"].values()) == 1


# ------------------------------------------------------------------ tracer
def test_disabled_span_is_shared_noop_singleton(monkeypatch):
    # without JAX there is no profiler to annotate: the disabled path is
    # the shared no-op singleton, allocating nothing
    monkeypatch.setattr(obs.trace, "_annotation_cls", False)
    s1 = obs.span("a", "main")
    s2 = obs.span("b", "other", nnz=5)
    assert s1 is s2                          # zero allocation on the fast path
    with s1 as inner:
        assert inner is s1
    assert obs.spans() == []


def test_disabled_span_with_jax_records_nothing_and_stays_cheap():
    # with JAX the disabled path enters the profiler annotation alone: a
    # span that records nothing into the ring buffer, at a bounded cost
    s = obs.span("plan.issue", "plan", mode=0)
    assert isinstance(s, obs.trace.Span) and not s._keep
    with s:
        assert s._ann is not None            # the profiler sees the span
    assert s._ann is None and obs.spans() == []
    assert obs.TRACING.dropped == 0

    n, best = 2000, float("inf")
    for _ in range(9):                       # best of 9: a loaded host's
        t0 = time.perf_counter()             # slow rounds do not count
        for _ in range(n):
            with obs.span("plan.issue", "plan", mode=0):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert obs.spans() == []
    assert best < 10e-6, f"{best * 1e6:.2f} us per disabled span"


def test_disabled_add_event_records_nothing():
    obs.add_event("x", "h2d", 0.0, 1.0, bytes=10)
    assert obs.spans() == []


def test_enabled_spans_record_nesting_and_attrs():
    obs.enable()
    with obs.span("outer", "scheduler", job=1) as outer:
        with obs.span("inner", "plan") as inner:
            inner.set(backend="streamed")
        obs.add_event("ev", "h2d", outer.start_s, outer.start_s + 0.5, n=3)
    got = obs.spans()
    names = {s.name: s for s in got}
    assert set(names) == {"outer", "inner", "ev"}
    assert names["inner"].parent == "outer"
    assert names["ev"].parent == "outer"     # add_event inherits the context
    assert names["outer"].parent is None
    assert names["inner"].attrs["backend"] == "streamed"
    assert names["ev"].duration_s == pytest.approx(0.5)
    assert names["outer"].end_s >= names["inner"].end_s


def test_ring_buffer_bounded_and_counts_drops():
    obs.enable(capacity=4)
    for i in range(10):
        with obs.span(f"s{i}", "main"):
            pass
    assert len(obs.spans()) == 4
    assert obs.TRACING.dropped == 6
    assert [s.name for s in obs.spans()] == ["s6", "s7", "s8", "s9"]
    drained = obs.drain()
    assert len(drained) == 4 and obs.spans() == []


def test_enabled_context_manager_restores_state():
    assert not obs.is_enabled()
    with obs.trace.enabled():
        assert obs.is_enabled()
        with obs.span("in", "main"):
            pass
    assert not obs.is_enabled()
    assert len(obs.spans()) == 1


def test_contextvar_parenting_is_per_thread():
    obs.enable()
    seen = []

    def worker():
        with obs.span("thread-span", "main") as s:
            seen.append(s.parent)

    with obs.span("main-span", "main"):
        th = threading.Thread(target=worker)
        th.start()
        th.join()
    assert seen == [None]                    # no cross-thread parent leakage


# --------------------------------------------------------------- exporters
def test_chrome_trace_structure():
    obs.enable()
    with obs.span("a", "dispatch", nnz=7):
        pass
    obs.add_event("b", "h2d", obs.TRACING.epoch_s, obs.TRACING.epoch_s + 1e-3)
    doc = obs.chrome_trace()
    json.dumps(doc)
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert {e["name"] for e in xs} == {"a", "b"}
    track_names = {e["args"]["name"] for e in metas
                   if e["name"] == "thread_name"}
    assert {"dispatch", "h2d"} <= track_names
    # distinct tracks get distinct tids; events carry their attrs
    tids = {e["cat"]: e["tid"] for e in xs}
    assert tids["dispatch"] != tids["h2d"]
    a = next(e for e in xs if e["name"] == "a")
    assert a["args"]["nnz"] == 7
    assert doc["otherData"]["dropped_spans"] == 0


def test_track_totals_sums_durations():
    obs.enable()
    obs.add_event("x", "h2d", 0.0, 0.25)
    obs.add_event("y", "h2d", 1.0, 1.5)
    obs.add_event("z", "store", 0.0, 0.125)
    tot = obs.track_totals()
    assert tot["h2d"] == pytest.approx(0.75)
    assert tot["store"] == pytest.approx(0.125)


def test_render_prometheus_format():
    from repro.service import ServiceMetrics
    m = ServiceMetrics()
    m.iterations_total = 7
    m.busy_time_s = 2.0
    m.tenant_iterations = {"a": 4, "b": 3}
    m.hist.quantum_s.record(0.5)
    text = obs.render_prometheus(m)
    assert "# TYPE repro_iterations_total counter" in text
    assert "repro_iterations_total 7" in text
    assert 'repro_tenant_iterations_total{tenant="a"} 4' in text
    assert "# TYPE repro_quantum_s histogram" in text
    assert 'repro_quantum_s_bucket{le="+Inf"} 1' in text
    assert "repro_quantum_s_count 1" in text
    assert "repro_iterations_per_busy_sec 3.5" in text
    assert "# TYPE repro_queue_depth gauge" in text


# ------------------------------------------------- end-to-end acceptance
def test_traced_disk_streamed_als_spans_match_stats(tmp_path):
    """The ISSUE acceptance path: a disk-streamed CP-ALS run with tracing
    enabled produces a Perfetto-loadable trace with distinct store-read /
    H2D-put / device-dispatch spans whose per-track duration sums agree
    with the EngineStats histogram totals (exactly, by construction)."""
    t = core.random_tensor((30, 24, 18), 2000, seed=1)
    b = core.build_blco(t, max_nnz_per_block=256)
    obs.enable()
    plan = plan_for(b, 1 << 30, rank=4, backend="disk_streamed",
                    store_path=str(tmp_path / "t.blco"))
    cp_als(plan, t.dims, 4, iters=2,
           norm_x=float(np.linalg.norm(t.values.astype(np.float64))),
           tol=0.0, seed=0)
    st = plan.stats()
    plan.close()
    obs.disable()

    names = {s.name for s in obs.spans()}
    assert {"store.read", "h2d.put", "dispatch.launch", "device.fence",
            "plan.mttkrp"} <= names
    tot = obs.track_totals()
    for track, stat_total in (("store", st.disk_time_s),
                              ("h2d", st.put_time_s),
                              ("dispatch", st.dispatch_time_s),
                              ("device", st.device_time_s)):
        assert tot[track] == pytest.approx(stat_total, rel=0.10), track
    # histogram sums equal the scalar totals (same samples)
    assert st.hist.put_chunk_s.sum == pytest.approx(st.put_time_s)
    assert st.hist.disk_read_s.sum == pytest.approx(st.disk_time_s)
    assert st.hist.dispatch_s.sum == pytest.approx(st.dispatch_time_s)
    assert st.hist.launch_nnz.count == st.launches
    assert int(st.hist.launch_nnz.sum) == b.nnz * st.mttkrp_calls
    # and the export is valid Chrome trace JSON
    doc = obs.write_chrome_trace(str(tmp_path / "trace.json"))
    with open(tmp_path / "trace.json") as f:
        assert json.load(f) == doc


def test_tracing_disabled_records_nothing_on_hot_path():
    t = core.random_tensor((20, 16, 12), 800, seed=2)
    b = core.build_blco(t, max_nnz_per_block=128)
    plan = plan_for(b, 1 << 30, rank=3, backend="streamed")
    plan.mttkrp(_factors(t.dims, rank=3), 0)
    st = plan.stats()
    plan.close()
    assert obs.spans() == []                 # nothing recorded...
    assert st.hist.dispatch_s.count == st.launches   # ...hists still fill


def test_service_trace_and_metrics_endpoints():
    from repro.service import (GetMetrics, GetTrace, ServiceRuntime,
                               SubmitDecomposition)
    t = core.random_tensor((20, 15, 10), 600, seed=3)
    obs.enable()
    with ServiceRuntime(device_budget_bytes=256 << 20) as rt:
        job = rt.submit(SubmitDecomposition(tensor=t, rank=3, iters=2,
                                            tol=0.0, tenant="t0"))
        rt.wait(job, timeout=300)
        m = rt.get_metrics()
        prom = rt.get_metrics(GetMetrics(format="prometheus"))
        doc = rt.trace(GetTrace(drain=True))
    obs.disable()
    json.dumps(m)
    assert m["iterations_total"] == 2
    assert m["busy_time_s"] > 0
    assert m["iterations_per_sec"] == pytest.approx(2 / m["busy_time_s"])
    assert "repro_busy_time_s" in prom
    names = {e["name"] for e in doc["traceEvents"] if e["ph"] == "X"}
    assert "scheduler.quantum" in names
    # quantum spans parent the sweep spans opened on the worker thread,
    # and the plan spans nest under the sweep's MTTKRP spans
    def parents(name):
        return {e["args"]["parent"] for e in doc["traceEvents"]
                if e["ph"] == "X" and e["name"] == name}
    assert parents("als.sweep") == {"scheduler.quantum"}
    assert parents("plan.mttkrp") == {"als.mttkrp"}
    assert obs.spans() == []                 # drain=True emptied the buffer
    with pytest.raises(ValueError):
        rt.service.get_metrics(GetMetrics(format="xml"))


# ---------------------------------------- program spans on the profiler clock
BLCO_PHASES = ("linearize", "sort", "block_keys", "reencode", "blocking",
               "batching")


def _in_memory_als(dims=(17, 13, 11), rank=5, iters=2):
    t = core.random_tensor(dims, 900, seed=4)
    b = core.build_blco(t, max_nnz_per_block=128)
    plan = plan_for(b, 1 << 30, rank=rank, backend="in_memory")
    cp_als(plan, t.dims, rank, iters=iters, tol=0.0, seed=0,
           norm_x=float(np.linalg.norm(t.values.astype(np.float64))))
    plan.close()
    return t


def test_program_span_tree():
    obs.enable()
    _in_memory_als()
    obs.disable()
    got = obs.spans()
    parents: dict = {}
    for s in got:
        parents.setdefault(s.name, set()).add(s.parent)
    assert parents["als.sweep"] == {None}
    assert parents["als.mttkrp"] == {"als.sweep"}
    assert parents["plan.mttkrp"] == {"als.mttkrp"}
    assert parents["plan.issue"] == {"plan.mttkrp"}
    assert parents["launch_cache.scan"] == {"plan.issue"}
    assert parents["plan.fence"] == {"plan.mttkrp"}
    assert parents["als.update"] == {"als.sweep"}
    assert parents["als.fit"] == {"als.sweep"}
    assert parents["blco.build"] == {None}
    for phase in BLCO_PHASES:
        assert parents[f"blco.{phase}"] == {"blco.build"}
    assert parents["plan.upload"] == {"engine.plan_for"}
    assert "device.fence" not in parents     # the fence is a real span now
    issues = [s for s in got if s.name == "plan.issue"]
    fences = [s for s in got if s.name == "plan.fence"]
    assert len(issues) == len(fences) == 6
    assert all(i.end_s <= f.start_s for i, f in zip(issues, fences))
    # an in-memory plan's spans are plan work, not pre-measured device time
    assert {s.track for s in issues + fences} == {"plan"}
    assert "device" not in obs.track_totals()
    # attributes name the work
    assert [s.attrs["iteration"] for s in got if s.name == "als.sweep"] \
        == [0, 1]
    assert [s.attrs["mode"] for s in got if s.name == "als.update"] == \
        [0, 1, 2, 0, 1, 2]
    assert [s.attrs["mode"] for s in issues] == [0, 1, 2, 0, 1, 2]


def test_profiler_trace_holds_program_spans(tmp_path):
    import gc
    import glob

    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        t = _in_memory_als()
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    assert obs.spans() == []                 # the ring buffer stayed off
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    events.setdefault(e.name, []).append(dict(e.stats))
    assert {"als.sweep", "als.mttkrp", "plan.mttkrp", "plan.issue",
            "launch_cache.scan", "plan.fence", "als.update", "als.fit",
            "blco.build", "engine.plan_for", "plan.upload"} <= set(events)
    assert {f"blco.{p}" for p in BLCO_PHASES} <= set(events)
    assert sorted(a["iteration"] for a in events["als.sweep"]) == [0, 1]
    assert sorted(a["mode"] for a in events["plan.issue"]) == \
        [0, 0, 1, 1, 2, 2]
    assert events["blco.build"][0]["nnz"] == t.nnz
    assert events["plan.mttkrp"][0]["backend"] == "in_memory"
    # the collector's pause, with its generation and what it collected
    full = [a for a in events["python.gc"] if a["generation"] == 2]
    assert full and all("collected" in a for a in full)


def test_tracing_off_records_nothing_and_raises_nothing():
    assert not obs.is_enabled()
    _in_memory_als()
    with obs.span("outer", "main", n=1) as sp:
        sp.set(backend="in_memory")
    assert obs.spans() == []


def test_compiles_counted_at_the_plan_boundary():
    import jax.numpy as jnp
    from repro.core.cp_als import cp_als_init, cp_als_step
    t = core.random_tensor((19, 14, 9), 700, seed=6)
    b = core.build_blco(t, max_nnz_per_block=128)
    plan = plan_for(b, 1 << 30, rank=6, backend="in_memory")
    state = cp_als_init(t.dims, 6, norm_x=1.0, tol=0.0, seed=0,
                        dtype=jnp.float32)
    obs.enable()
    cp_als_step(plan, state)
    first = plan.stats().compiles
    cp_als_step(plan, state)
    cp_als_step(plan, state)
    obs.disable()
    plan.close()
    assert first > 0
    assert plan.stats().compiles == first    # later sweeps compile nothing
    assert plan.stats().snapshot()["compiles"] == first
    compiles = [s for s in obs.spans() if s.name == "jit.compile"]
    assert compiles and all(s.parent is not None for s in compiles)
    assert {"launch_cache.scan", "plan.issue"} & {s.parent for s in compiles}
    assert all(s.duration_s == pytest.approx(s.attrs["seconds"])
               for s in compiles)


def test_blco_phase_spans_match_construction_stats():
    t = core.random_tensor((300, 200, 100), 200_000, seed=5)
    obs.enable()
    b = core.build_blco(t)
    obs.disable()
    by = {s.name: s for s in obs.spans()}
    assert tuple(b.construction_stats) == BLCO_PHASES
    for phase in BLCO_PHASES:
        assert b.construction_stats[phase] == by[f"blco.{phase}"].duration_s
    phases = sum(by[f"blco.{p}"].duration_s for p in BLCO_PHASES)
    assert phases == pytest.approx(by["blco.build"].duration_s, rel=0.02)
