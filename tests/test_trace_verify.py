"""Trace-tier verifier suite (ISSUE 9).

Each verifier family gets at least one known-bad fixture it must reject
(a hand-broken layout, an unbounded rounding, a callback/narrowing jaxpr,
an unsound ``unique_indices`` claim) plus a clean fixture it must accept,
alongside the integration checks: the committed tree verifies clean, the
write-conflict prover's per-launch report feeds the segmented-reduction
invariant test, and the encoding verifier round-trips against the host
and device delinearizers — property-tested under hypothesis where
available, with the adversarial corners pinned deterministically so the
coverage survives the stub.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import hypothesis_or_stub

from repro import core
from repro.analysis.trace import (DEFAULT_CONFIGS, PASS_CALLBACK,
                                  PASS_CHURN, PASS_CONFLICT, PASS_ENCODING,
                                  PASS_NARROWING, TRACE_PASS_IDS,
                                  audit_callbacks, audit_hot_path,
                                  audit_narrowing, audit_reservation_churn,
                                  audit_tenant_invariance,
                                  check_scatter_claims,
                                  check_write_structure, conflict_report,
                                  prove_encoding, prove_variant,
                                  registered_hot_paths, run_trace_tier,
                                  scatter_facts, trace_jaxpr, verify_layout)
from repro.analysis.trace.cachekeys import audit_rounding, churn_bound
from repro.core import linearize as lin
from repro.core import u64
from repro.core.launches import LaunchCache
from repro.core.padding import LANE, pad_multiple
from repro.kernels.fused import fused_cache_mttkrp
from repro.kernels.ref import delinearize_ref

given, settings, st = hypothesis_or_stub()

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


# ------------------------------------------------------------ jaxpr audits
def test_callback_fixture_rejected():
    """Known-bad: a pure_callback staged inside a jitted region."""
    def bad(x):
        y = jax.pure_callback(lambda a: a,
                              jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return y + 1.0

    closed = trace_jaxpr(jax.jit(bad), _f32(8))
    fs = audit_callbacks(closed, path="tests/fixture.py", symbol="bad")
    assert fs and all(f.pass_id == PASS_CALLBACK for f in fs)
    assert "host round-trip" in fs[0].message
    # the walk found it inside the jit sub-jaxpr, not at top level
    assert "(at jit," in fs[0].message


def test_callback_audit_clean_on_plain_math():
    closed = trace_jaxpr(jax.jit(lambda x: (x * 2.0).sum()), _f32(8))
    assert audit_callbacks(closed, path="p", symbol="s") == []


def test_narrowing_fixture_rejected():
    """Known-bad: f32 values squeezed to bf16 ahead of a scatter-add."""
    def bad(vals, idx):
        small = vals.astype(jnp.bfloat16)
        out = jnp.zeros((16,), jnp.bfloat16)
        return out.at[idx].add(small)

    closed = trace_jaxpr(bad, _f32(32), _i32(32))
    fs = audit_narrowing(closed, path="tests/fixture.py", symbol="bad")
    assert fs and all(f.pass_id == PASS_NARROWING for f in fs)
    assert "scatter-add" in fs[0].message


def test_narrowing_taint_survives_rewidening():
    """Re-widening after the lossy convert must NOT clear the finding."""
    def bad(vals, idx):
        laundered = vals.astype(jnp.bfloat16).astype(jnp.float32)
        out = jnp.zeros((16,), jnp.float32)
        return out.at[idx].add(laundered)

    closed = trace_jaxpr(bad, _f32(32), _i32(32))
    assert audit_narrowing(closed, path="p", symbol="s")


def test_narrowing_ignores_integer_converts():
    """Index math between integer widths is not precision loss."""
    def fine(vals, idx):
        out = jnp.zeros((16,), jnp.float32)
        return out.at[idx.astype(jnp.int16).astype(jnp.int32)].add(vals)

    closed = trace_jaxpr(fine, _f32(32), _i32(32))
    assert audit_narrowing(closed, path="p", symbol="s") == []


def test_registered_hot_paths_audit_clean():
    """The six shipped hot paths carry no callbacks and no narrowing."""
    paths = registered_hot_paths()
    assert len(paths) == 6
    for hp in paths:
        assert audit_hot_path(hp) == [], hp.name


# -------------------------------------------------------- cache-key churn
def test_pad_multiple_reservation_is_the_known_bad_rounding():
    """Raw LANE rounding yields one executable per LANE step: unbounded."""
    fs = audit_rounding("raw_lane", pad_multiple)
    assert fs and fs[0].pass_id == PASS_CHURN
    assert "distinct reservations" in fs[0].message


def test_shipped_roundings_bounded_and_tenant_invariant():
    assert audit_reservation_churn() == []
    assert audit_tenant_invariance() == []
    assert audit_tenant_invariance(n_tenants=5000) == []


def test_unsound_roundings_rejected():
    # under-covering: reservation smaller than the launch overflows
    fs = audit_rounding("undersized", lambda n: max(LANE, n - 1))
    assert fs and "smaller than launch nnz" in fs[0].message
    # non-monotone: a bigger launch must never shrink its reservation
    fs = audit_rounding("sawtooth",
                        lambda n: 2 * LANE if n % 2 else 4 * LANE)
    assert fs and "not monotone" in fs[0].message


def test_churn_bound_is_logarithmic_in_range():
    assert churn_bound(1 << 18) == 16 * 19
    assert churn_bound(1 << 24) - churn_bound(1 << 18) == 16 * 6


# ------------------------------------------------------- encoding proofs
def _spec_864():
    spec = lin.LinearSpec.make((8, 6, 4))
    return spec, lin.reencode_spec(spec, 64)


def test_default_config_sweep_proves_clean():
    for dims, target in DEFAULT_CONFIGS:
        proof, fs = prove_encoding(dims, target_bits=target)
        assert fs == [], (dims, target, [f.message for f in fs])
        assert proof is not None
        assert proof.stored_bits <= target and proof.key_bits <= 64
        assert proof.max_coord == tuple(d - 1 for d in dims)
        assert proof.padded_lane_noop


def test_lossy_spec_rejected_and_roundtrip_actually_fails():
    """Known-bad: drop one field bit without moving it to the block key.

    The verifier must flag the broken partition, and the break is real:
    the dropped bit is stored nowhere, so the witness coordinate 5
    (binary 101) decodes to 1 under the mutilated layout.
    """
    spec, re = _spec_864()
    lossy = lin.ReencodeSpec((2,) + re.field_bits[1:], re.field_shift,
                             re.block_bits)
    fs = verify_layout((8, 6, 4), spec, lossy, symbol="lossy")
    assert any("drops or invents" in f.message for f in fs)
    witness = 5                      # bit 2 set, beyond the 2-bit field
    fb, bb = lossy.field_bits[0], lossy.block_bits[0]
    decoded = (((witness >> fb) & ((1 << bb) - 1)) << fb) \
        | (witness & ((1 << fb) - 1))
    assert decoded != witness


def test_overlapping_fields_rejected():
    spec, re = _spec_864()
    clash = lin.ReencodeSpec(re.field_bits, (0, 0, 6), re.block_bits)
    fs = verify_layout((8, 6, 4), spec, clash, symbol="clash")
    assert any("overlaps" in f.message for f in fs)


def test_mask_overflow_at_u64_boundary_rejected():
    spec, re = _spec_864()
    wrap = lin.ReencodeSpec(re.field_bits, (0, 3, 63), re.block_bits)
    fs = verify_layout((8, 6, 4), spec, wrap, symbol="wrap")
    assert any("overflows the 64-bit" in f.message for f in fs)


def test_oversized_field_and_extent_rejected():
    # bypass LinearSpec.make's guard to reach the verifier's own checks
    spec = lin.LinearSpec(dims=(1 << 33,), bits=(33,),
                          positions=(tuple(range(33)),), total_bits=33)
    re = lin.ReencodeSpec((33,), (0,), (0,))
    fs = verify_layout((1 << 33,), spec, re, symbol="huge")
    msgs = [f.message for f in fs]
    assert any("> 32" in m for m in msgs)
    assert any("2^31" in m for m in msgs)


def test_alto_bijection_violation_rejected():
    spec, re = _spec_864()
    broken = lin.LinearSpec(spec.dims, spec.bits,
                            ((0, 1, 2), (0, 4, 5), (6, 7)),  # bit 0 doubled
                            spec.total_bits)
    fs = verify_layout((8, 6, 4), broken, re, symbol="dup")
    assert any("not a bijection" in f.message for f in fs)


def test_construction_guard_is_witnessed_not_crashed():
    with pytest.raises(ValueError, match="2\\^31"):
        lin.LinearSpec.make((2**31 + 1, 4))
    proof, fs = prove_encoding((2**31 + 1, 4))
    assert proof is None
    assert len(fs) == 1 and "construction rejected" in fs[0].message


def test_int32_boundary_exactly_legal():
    proof, fs = prove_encoding((2**31, 4))
    assert fs == [] and proof is not None
    assert proof.max_coord == (2**31 - 1, 3)


# --------------------------------------------------- encoding round trips
def _roundtrip_rows(spec, re, coords):
    """Full shipped pipeline, one block at a time: encode -> key ->
    upper -> stored -> host delinearize."""
    out = np.zeros_like(coords)
    hi, lo = lin.alto_encode(spec, coords)
    keys = lin.block_key(spec, re, hi, lo)
    stored = lin.reencode(spec, re, coords)
    for i in range(coords.shape[0]):
        upper = lin.key_to_upper_coords(spec, re, int(keys[i]))
        out[i] = lin.delinearize_host(re, stored[i:i + 1], upper)[0]
    return out


def test_roundtrip_blocked_layout_host_and_device():
    """target_bits=12 forces blocking on the tests' (40,25,30) shape; the
    host oracle and the device delinearizer must both invert it."""
    dims = (40, 25, 30)
    spec = lin.LinearSpec.make(dims)
    re = lin.reencode_spec(spec, 12)
    assert verify_layout(dims, spec, re) == []
    rng = np.random.default_rng(3)
    coords = np.stack([rng.integers(0, d, 64) for d in dims], axis=1)
    # pin every extent edge — the exact coordinates check (5) reasons about
    coords[0] = [d - 1 for d in dims]
    coords[1] = 0
    assert np.array_equal(_roundtrip_rows(spec, re, coords), coords)

    # device path: same stored words through kernels.ref.delinearize_ref
    hi_a, lo_a = lin.alto_encode(spec, coords)
    keys = lin.block_key(spec, re, hi_a, lo_a)
    stored = lin.reencode(spec, re, coords)
    bases = np.stack([
        lin.key_to_upper_coords(spec, re, int(k)) <<
        np.array(re.field_bits, np.int64) for k in keys]).astype(np.int32)
    hi32, lo32 = u64.split64(stored)
    dec = delinearize_ref(jnp.asarray(hi32), jnp.asarray(lo32),
                          jnp.asarray(bases), field_bits=re.field_bits,
                          field_shifts=re.field_shift)
    assert np.array_equal(np.asarray(dec), coords)


def test_roundtrip_near_64bit_stored_word():
    """Adversarial corner: fields fill all 64 stored bits and one field
    straddles the uint32 word boundary of the (hi, lo) split."""
    dims = (2**31, 2**31, 4)
    spec = lin.LinearSpec.make(dims)
    re = lin.reencode_spec(spec, 64)
    assert sum(re.field_bits) == 64
    assert verify_layout(dims, spec, re) == []
    assert any(s < 32 < s + f
               for s, f in zip(re.field_shift, re.field_bits) if f)
    coords = np.array([[2**31 - 1, 2**31 - 1, 3],
                       [0, 0, 0],
                       [2**31 - 1, 0, 3],
                       [1, 2**31 - 1, 0],
                       [2**30, 2**30 + 1, 2]], np.int64)
    assert np.array_equal(_roundtrip_rows(spec, re, coords), coords)
    stored = lin.reencode(spec, re, coords)
    hi32, lo32 = u64.split64(stored)
    dec = delinearize_ref(jnp.asarray(hi32), jnp.asarray(lo32),
                          jnp.zeros((5, 3), jnp.int32),
                          field_bits=re.field_bits,
                          field_shifts=re.field_shift)
    assert np.array_equal(np.asarray(dec), coords)


@given(data=st.data())
@settings(max_examples=30, deadline=None)
def test_property_accepts_iff_roundtrip(data):
    """Verifier accepts a shipped layout ⇔ the encoding round-trips.

    Random dims up to the int32 ceiling with random target widths: when
    the proof succeeds, every sampled coordinate (extent edges included)
    must survive encode -> block key -> stored -> delinearize bit-exactly;
    when the proof fails, the failure must name the block-key overflow
    and ``block_key`` itself must refuse the same layout.
    """
    n_modes = data.draw(st.integers(min_value=2, max_value=4))
    dims = tuple(data.draw(st.integers(min_value=1, max_value=2**31))
                 for _ in range(n_modes))
    target = data.draw(st.sampled_from((8, 16, 32, 64)))
    proof, fs = prove_encoding(dims, target_bits=target)
    spec = lin.LinearSpec.make(dims)
    re = lin.reencode_spec(spec, target)
    if proof is None:
        assert fs and all("block key" in f.message for f in fs)
        with pytest.raises(ValueError):
            lin.block_key(spec, re, np.zeros(1, np.uint64),
                          np.zeros(1, np.uint64))
        return
    rows = [tuple(d - 1 for d in dims), tuple(0 for _ in dims)]
    for _ in range(6):
        rows.append(tuple(data.draw(st.integers(0, d - 1)) for d in dims))
    coords = np.array(rows, np.int64)
    assert np.array_equal(_roundtrip_rows(spec, re, coords), coords)


@given(data=st.data())
@settings(max_examples=30, deadline=None)
def test_property_broken_partition_always_flagged(data):
    """Dropping any field bit (without re-homing it) must be rejected."""
    n_modes = data.draw(st.integers(min_value=2, max_value=4))
    dims = tuple(data.draw(st.integers(min_value=2, max_value=2**20))
                 for _ in range(n_modes))
    spec = lin.LinearSpec.make(dims)
    re = lin.reencode_spec(spec, 64)
    mode = data.draw(st.integers(0, n_modes - 1))
    fields = list(re.field_bits)
    fields[mode] -= 1
    lossy = lin.ReencodeSpec(tuple(fields), re.field_shift, re.block_bits)
    fs = verify_layout(dims, spec, lossy, symbol="mutated")
    assert any(f"mode {mode}" in f.message and "drops or invents"
               in f.message for f in fs)


# ------------------------------------------------------- conflict prover
def test_fused_variants_prove_clean():
    for variant in ("segment", "stash"):
        facts, fs = prove_variant(variant)
        assert fs == [], (variant, [f.message for f in fs])
        assert any(f["primitive"] == "pallas_call" for f in facts)


def test_segment_kernel_declares_its_conflicts():
    facts, _ = prove_variant("segment")
    outer = [f for f in facts if f["primitive"] == "scatter-add"
             and not f.get("inside_pallas")]
    assert len(outer) == 1
    assert outer[0]["unique_indices"] is False


def test_stash_kernel_has_no_outer_scatter():
    facts, _ = prove_variant("stash")
    assert not any(f["primitive"].startswith("scatter")
                   for f in facts if not f.get("inside_pallas"))


def test_write_structure_fixtures_rejected():
    pallas = {"primitive": "pallas_call", "context": "<top>"}

    def scatter(unique):
        return {"primitive": "scatter-add", "unique_indices": unique,
                "inside_pallas": False, "context": "<top>"}

    # no pallas_call at all: the "fused" kernel is not fused
    fs = check_write_structure([scatter(False)], variant="segment",
                               symbol="s")
    assert any("not fused" in f.message for f in fs)
    # stash must keep ALL accumulation inside the sequential grid
    fs = check_write_structure([pallas, scatter(False)], variant="stash",
                               symbol="s")
    assert any("stash variant stages" in f.message for f in fs)
    # segment: exactly one deferred apply, and it must admit duplicates
    fs = check_write_structure([pallas, scatter(False), scatter(False)],
                               variant="segment", symbol="s")
    assert any("expected exactly one" in f.message for f in fs)
    fs = check_write_structure([pallas, scatter(True)], variant="segment",
                               symbol="s")
    assert any("unique_indices=True" in f.message for f in fs)
    assert all(f.pass_id == PASS_CONFLICT for f in fs)
    # and the real shapes pass
    assert check_write_structure([pallas, scatter(False)],
                                 variant="segment", symbol="s") == []
    assert check_write_structure([pallas], variant="stash",
                                 symbol="s") == []


def test_unique_claim_fixture_rejected():
    """Known-bad: a scatter claiming uniqueness over a duplicate-capable
    write set — the claim licenses XLA to drop conflict handling."""
    def bad(vals, idx):
        out = jnp.zeros((16,), jnp.float32)
        return out.at[idx].add(vals, unique_indices=True)

    closed = trace_jaxpr(bad, _f32(32), _i32(32))
    fs = check_scatter_claims(closed, duplicates_possible=True,
                              path="tests/fixture.py", symbol="bad")
    assert len(fs) == 1 and "unique_indices=True" in fs[0].message
    # the same claim is fine when duplicates are proven impossible
    assert check_scatter_claims(closed, duplicates_possible=False,
                                path="p", symbol="s") == []

    def fine(vals, idx):
        out = jnp.zeros((16,), jnp.float32)
        return out.at[idx].add(vals)

    assert check_scatter_claims(trace_jaxpr(fine, _f32(32), _i32(32)),
                                duplicates_possible=True,
                                path="p", symbol="s") == []


def _demo_blco():
    t = core.random_tensor((40, 25, 30), 2000, seed=1, dist="powerlaw")
    return t, core.build_blco(t, target_bits=12, max_nnz_per_block=256)


def test_conflict_report_accounting():
    t, b = _demo_blco()
    report = conflict_report(b, 0)
    assert report["dims"] == [40, 25, 30]
    assert sum(l["nnz"] for l in report["launches"]) == t.nnz
    for l in report["launches"]:
        assert l["padded_nnz"] == report["reservation"]
        assert l["tiles"] * report["tile"] == l["padded_nnz"]
        # segment count brackets: >= one per distinct row touched,
        # <= one per padded slot
        assert l["distinct_rows"] <= l["segments"] <= l["padded_nnz"]
        assert l["segments"] + l["padding_segments"] >= l["tiles"]
        if l["max_writers_per_row"] > 1:
            assert l["conflict_rows"]
    assert report["total_segments"] == sum(l["segments"]
                                           for l in report["launches"])
    json.dumps(report)


def test_segmented_reduction_invariant():
    """The acceptance-criterion invariant: the report proves the fused
    scatter's write set contains duplicate rows, the kernel's traced form
    declares exactly that (unique_indices=False), and under that conflict
    structure the segmented reduction still reproduces the oracle."""
    t, b = _demo_blco()
    report = conflict_report(b, 0)
    assert report["max_writers_per_row_per_step"] >= 2
    assert report["unique_indices_sound"] is False

    facts, _ = prove_variant("segment")
    outer = [f for f in facts if f["primitive"] == "scatter-add"
             and not f.get("inside_pallas")]
    assert outer and not outer[0]["unique_indices"], \
        "kernel claim contradicts the conflict report"

    factors = [np.random.default_rng(0).standard_normal(
        (d, 8)).astype(np.float32) for d in b.dims]
    cache = LaunchCache.from_blco(b)
    out = fused_cache_mttkrp(cache, factors, 0, resolution="register")
    oracle = core.mttkrp_dense_oracle(t, factors, 0)
    err = np.max(np.abs(np.asarray(out, np.float64) - oracle)) / \
        (np.max(np.abs(oracle)) + 1e-30)
    assert err < 5e-4


def test_conflict_free_tensor_is_reported_sound():
    """Distinct rows, zero padding: the one case unique_indices would be
    admissible — the report must recognize it rather than cry wolf."""
    idx = np.stack([np.arange(256), np.zeros(256, np.int64),
                    np.zeros(256, np.int64)], axis=1)
    t = core.from_coo(idx, np.ones(256, np.float32), (256, 2, 2))
    b = core.build_blco(t, target_bits=64, max_nnz_per_block=1 << 20)
    report = conflict_report(b, 0)
    assert len(report["launches"]) == 1
    assert report["launches"][0]["padding_segments"] == 0
    assert report["max_writers_per_row_per_step"] == 1
    assert report["unique_indices_sound"] is True


# -------------------------------------------------------- tier integration
def test_trace_tier_clean_on_committed_tree():
    findings, bundle, m = run_trace_tier()
    assert findings == [], [f.message for f in findings]
    assert m.hot_paths_traced == 6
    assert m.encodings_verified == len(DEFAULT_CONFIGS)
    assert m.jaxpr_eqns_walked > 0 and m.launches_analyzed > 0
    assert m.findings_total == 0
    assert set(bundle) == {"conflict_report", "encoding_proofs", "metrics"}
    assert len(bundle["encoding_proofs"]) == len(DEFAULT_CONFIGS)
    json.dumps(bundle)
    assert len(TRACE_PASS_IDS) == 5


def test_lint_cli_trace_tier_and_tier_scoped_staleness(tmp_path):
    """End-to-end CLI: --tier=trace exits 0 on the committed tree, writes
    the artifact bundle, and does NOT treat an AST-tier baseline entry as
    stale when only the trace tier ran."""
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps({"suppressions": [{
        "pass": "dtype-promotion", "path": "src/repro/nonexistent.py",
        "symbol": "ghost", "reason": "ast-tier entry; not this tier's call",
    }]}))
    report = tmp_path / "bundle.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "lint.py"),
         "--tier=trace", "--format", "json",
         "--baseline", str(baseline), "--report-out", str(report)],
        capture_output=True, text=True, cwd=REPO, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    out = json.loads(proc.stdout)
    assert out["findings"] == []
    assert out["stale_baseline_entries"] == []
    bundle = json.loads(report.read_text())
    assert bundle["conflict_report"]["launches"]
    assert bundle["metrics"]["hot_paths_traced"] == 6


def test_lint_cli_stale_baseline_fails_and_prunes(tmp_path):
    """A stale suppression fails the run; --prune-baseline repairs it."""
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps({"suppressions": [{
        "pass": "host-sync-in-hot-path", "path": "src/repro/nonexistent.py",
        "symbol": "ghost", "reason": "finding long since fixed",
    }]}))
    cmd = [sys.executable, os.path.join(REPO, "scripts", "lint.py"),
           "--tier=ast", "--baseline", str(baseline)]
    proc = subprocess.run(cmd, capture_output=True, text=True, cwd=REPO,
                          timeout=300)
    assert proc.returncode == 1
    assert "stale baseline entry" in proc.stdout
    proc = subprocess.run(cmd + ["--prune-baseline"], capture_output=True,
                          text=True, cwd=REPO, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "pruned 1 stale entry" in proc.stdout
    assert json.loads(baseline.read_text())["suppressions"] == []
    proc = subprocess.run(cmd, capture_output=True, text=True, cwd=REPO,
                          timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
