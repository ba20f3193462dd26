"""The four-chip cell: its files, the mesh and budget the harness builds
from its traffic, whole runs on four XLA:CPU devices, and its readers.

The runs skip only the harness's look for a chip: in a child process with
four CPU devices (the device count is fixed before JAX starts) ``run_cell``
makes the cell's tensor (cut to a few thousand nnz, dims kept), plans it
over the mesh, warms up, runs the window and compares.  A sound run is
correct under the cell's committed limits and compiles nothing in the
window; the control (the reference in bfloat16) and each planted fault
are not correct.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from bench import run, xplane

ROOT = Path(__file__).resolve().parents[2]
CELL = "nell2.sharded4"
IN_MEMORY = ["nell2.in_memory", "chicago.in_memory"]
# the readers of the sharded module: the MTTKRP's, widened to it, and the
# exchange's own
SHARDED_READERS = ("mttkrp.device_ms", "sharded.allreduce_ms",
                   "mttkrp_roofline", "als.outside_mttkrp_ms")
GIB = 1 << 30
MS = 10 ** 6

CHILD = """
    import contextlib, io, json, sys
    import jax, jax.numpy as jnp, numpy as np
    from bench import run
    import repro.core.blco as blco
    import repro.core.distributed as distributed
    from repro import core

    blco.default_launch_nnz = lambda *a: 1024
    PEAKS = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}
    clock = run.CompileClock(jax)
    devs = jax.devices()[:4]
    out = {}

    def small_run(**kw):
        cell = run.load_cell("nell2.sharded4")
        cell["config"] = dict(cell["config"], nnz=6000)
        said = io.StringIO()
        with contextlib.redirect_stdout(said):
            line = run.run_cell(cell, 2 ** 33 + 7, 0.5, False, devices=devs,
                                bytes_limits=[8 << 30] * 4, peaks=PEAKS,
                                t_start=0.0, clock=clock, **kw)
        line["said"] = said.getvalue()
        return line

    traffic = run.load_cell("nell2.sharded4")["traffic"]
    mesh = run.cell_mesh(traffic, devs)
    budget = run.device_budget(traffic["budget"], [8 << 30] * 4)
    from repro.engine import plan_for
    t = core.random_tensor((64, 33, 17), 4000, seed=5, dist="powerlaw")
    plan = plan_for(core.build_blco(t), budget, rank=8, mesh=mesh)
    out["plan"] = {"mesh": dict(mesh.shape), "budget": budget,
                   "backend": plan.backend,
                   "demotions": plan.stats().demotions}
    plan.close()

    b = core.build_blco(t)
    shards = distributed.shard_launch_arrays(b, 4)
    fn = distributed.sharded_mttkrp_fn(
        mesh, re_fields=b.re.field_bits, re_shifts=b.re.field_shift,
        out_rows=64, mode=0, step=shards["vals"].shape[1])
    factors = tuple(jnp.ones((d, 8), jnp.float32) for d in t.dims)
    hlo = fn.lower(shards["idx_hi"], shards["idx_lo"], shards["vals"],
                   shards["bases"], factors).compile().as_text()
    out["module"] = hlo.split()[1].rstrip(",")

    sound = small_run(controls=("bfloat16",))
    out["sound"] = {"correct": sound["correct"], "checks": sound["checks"],
                    "said": sound["said"]}
    out["control"] = sound["control"]["bfloat16"]

    real_step, real_scan = core.cp_als_step, distributed.stacked_mttkrp
    real_psum = jax.lax.psum

    def half(hi, lo, vals, bases, factors, **kw):
        keep = (jnp.arange(vals.size) % 2 == 0).reshape(vals.shape)
        return 2 * real_scan(hi, lo, jnp.where(keep, vals, 0), bases,
                             factors, **kw)

    def altered(*args, **kw):
        o = real_scan(*args, **kw)
        return o.at[0].set(o[1])

    faults = {
        "state_unchanged": (core, "cp_als_step", lambda fn, state: state),
        "half_the_nonzeros": (distributed, "stacked_mttkrp", half),
        "exchange_left_out": (jax.lax, "psum", lambda x, axis_name: x),
        "answer_altered": (distributed, "stacked_mttkrp", altered),
    }
    for name, (mod, attr, broken) in faults.items():
        real = getattr(mod, attr)
        setattr(mod, attr, broken)
        try:
            line = small_run()
        finally:
            setattr(mod, attr, real)
        out[name] = {"correct": line["correct"], "checks": line["checks"]}
    print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def child():
    """The child's findings: the plan, the module name, a sound run with
    its control, and one run per planted fault."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT), str(ROOT / "src")])
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(CHILD)],
                       capture_output=True, text=True, env=env, timeout=900,
                       cwd=ROOT)
    assert p.returncode == 0, p.stderr[-4000:]
    line = next(ln for ln in p.stdout.splitlines()
                if ln.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def test_load_cell_reads_traffic_limits_and_metrics():
    cell = run.load_cell(CELL)
    assert cell["chips"] == 4
    traffic = cell["traffic"]
    assert traffic["regime"] == "sharded"
    assert traffic["budget"] == dict(traffic["budget"],
                                     rule="device_limit_less", bytes=GIB)
    assert set(cell["limits"]) == set(run.check.NUMBERS)
    assert [m["name"] for m in cell["end_to_end"]] == ["sweep_s", "setup_s"]
    names = {m["name"] for m in cell["per_layer"]}
    assert set(SHARDED_READERS) <= names
    assert {"device.idle_share", "device.peak_hbm_bytes",
            "setup.blco_build_s", "setup.compile_s", "als.update_idle_ms",
            "als.fit_idle_ms", "host.gc_ms"} <= names
    # readers of InMemoryPlan's own spans stay out
    assert not names & {"mttkrp.issue_idle_ms", "mttkrp.fence_idle_ms"}
    for m in cell["per_layer"]:
        assert callable(run.metric_reader(m["name"]))


@pytest.mark.parametrize("name", IN_MEMORY)
def test_in_memory_cell_keeps_its_budget_plan_and_readings(name):
    cell = run.load_cell(name)
    traffic = cell["traffic"]
    assert run.cell_mesh(traffic, []) is None
    assert run.device_budget(traffic["budget"], [16 * GIB]) == 15 * GIB
    names = {m["name"] for m in cell["per_layer"]}
    assert "sharded.allreduce_ms" not in names
    assert {"mttkrp.device_ms", "mttkrp_roofline", "als.outside_mttkrp_ms",
            "mttkrp.issue_idle_ms", "mttkrp.fence_idle_ms"} <= names


def test_budget_rules():
    rule = {"rule": "device_limit_less", "bytes": GIB}
    limits = [16 * GIB, 16 * GIB, 15 * GIB, 16 * GIB]
    assert run.device_budget(rule, limits[:1]) == 15 * GIB
    assert run.device_budget(rule, limits) == 59 * GIB
    with pytest.raises(ValueError):
        run.device_budget({"rule": "guess", "bytes": 0}, limits)


def test_mesh_and_budget_pick_the_sharded_plan(child):
    plan = child["plan"]
    assert plan["mesh"] == {"data": 4, "model": 1}
    assert plan["budget"] == 4 * ((8 << 30) - GIB)
    assert plan["backend"] == "sharded" and plan["demotions"] == 0


def test_sharded_module_name_is_what_the_readers_read(child):
    for name in SHARDED_READERS:
        module = _reader_module(name)
        names = getattr(module, "MTTKRP_MODULES", None) \
            or (module.SHARDED_MODULE,)
        assert child["module"] in names


def test_sound_run_is_correct_and_compiles_nothing_in_the_window(child):
    sound = child["sound"]
    assert sound["correct"], sound["checks"]
    assert "plan: sharded" in sound["said"]
    assert ", 0 programs compiled inside it" in sound["said"]
    # the second sweep compiles each mode again, the third nothing
    assert "warmup_sweeps 3," in sound["said"]


def test_the_control_is_not_correct(child):
    limits = run.load_cell(CELL)["limits"]
    assert not run.check.verdict({"numbers": child["control"],
                                  "faults": []}, limits), child["control"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_the_nonzeros",
                                   "exchange_left_out", "answer_altered"])
def test_planted_fault_is_not_correct(child, fault):
    assert not child[fault]["correct"], child[fault]["checks"]


def _reader_module(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", run.BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def synthetic(module="jit__shard_fn"):
    """Two devices, two sharded calls each in a 100-ms window: device 0's
    modules take 30 and 20 ms with all-reduces of 2 and 1 ms, device 1's
    34 and 16 ms with 4 and 1 ms."""
    def device(calls):
        mods, ops = [], []
        for start, length, ar in calls:
            mods.append((start * MS, (start + length) * MS, f"{module}(7)"))
            ops.append((start * MS, (start + length - ar) * MS,
                        f"{module}/fusion.30"))
            ops.append(((start + length - ar) * MS, (start + length) * MS,
                        f"{module}/psum.7"))
        return mods, ops
    d0 = device([(10, 30, 2), (60, 20, 1)])
    d1 = device([(10, 34, 4), (60, 16, 1)])
    return xplane.DeviceTrace(
        ops=[d0[1], d1[1]], modules=[d0[0], d1[0]],
        spans=[(0, 100 * MS, "bench.window"), (0, 50 * MS, "bench.sweep"),
               (50 * MS, 100 * MS, "bench.sweep")],
        opcodes={f"{module}/fusion.30": "fusion",
                 f"{module}/psum.7": "all-reduce"})


def record(tr):
    return {"trace": tr, "call_modes": [0, 1], "dims": (12092, 9184, 28818),
            "nnz": 25_000_000, "rank": 32, "value_dtype": "float32",
            "chips": 4,
            "peaks": {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}}


def test_sharded_readers_average_over_devices_and_calls():
    rec = record(synthetic())
    # (30 + 20 + 34 + 16) / 2 devices / 2 calls
    assert run.metric_reader("mttkrp.device_ms")(rec) == pytest.approx(25.0)
    # each sweep less its module time on the mean device: (50 - 32 + 50 - 18)
    # / 2
    assert run.metric_reader("als.outside_mttkrp_ms")(rec) == \
        pytest.approx(25.0)
    # (2 + 1 + 4 + 1) / 2 / 2
    assert run.metric_reader("sharded.allreduce_ms")(rec) == \
        pytest.approx(2.0)
    from bench import work
    mesh = {"hbm_bytes_per_s": 4 * 819e9, "flops_per_s": 4 * 197e12}
    least = sum(work.least_seconds(rec["dims"], rec["nnz"], 32, "float32",
                                   m, mesh) for m in (0, 1))
    assert run.metric_reader("mttkrp_roofline")(rec) == \
        pytest.approx(100 * least / 0.050)


@pytest.mark.parametrize("name", SHARDED_READERS)
def test_sharded_readers_return_none_without_the_module(name):
    assert run.metric_reader(name)(record(synthetic("jit_other"))) is None
    assert run.metric_reader(name)(dict(record(None))) is None


def test_allreduce_reader_returns_none_without_a_collective():
    tr = synthetic()
    tr.opcodes = {n: "fusion" for n in tr.opcodes}
    assert run.metric_reader("sharded.allreduce_ms")(record(tr)) is None
    assert run.metric_reader("mttkrp.device_ms")(record(tr)) == \
        pytest.approx(25.0)


@pytest.mark.parametrize("hlo, want", [
    ("%psum.7 = f32[12092,32]{1,0:T(8,128)S(1)} all-reduce(%fusion.4), "
     "channel_id=1, replica_groups={{0,1,2,3}}", "all-reduce"),
    ("%copy-start = (f32[32,32]{1,0:T(8,128)S(1)}, u32[]{:S(2)}) "
     "copy-start(f32[32,32]{1,0:T(8,128)} %factors_3_.1)", "copy-start"),
    ("%and_convert_fusion = s32[1,20480]{1,0:T(1,128)S(1)} "
     "fusion(u32[1,20480]{1,0:T(1,128)} %lo.1), kind=kLoop", "fusion"),
    ("while.1", ""),
])
def test_opcode_of_an_hlo_instruction(hlo, want):
    assert xplane.opcode(hlo) == want
