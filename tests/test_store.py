"""Persistent BLCO store: format roundtrip, corruption detection,
disk-streamed execution, registry spill tier + LRU, restart stability."""
import os
import weakref

import numpy as np
import pytest

from repro import core
from repro.core.streaming import LaunchChunks
from repro.engine import factor_bytes, in_memory_bytes, plan_for
from repro.engine.plans import InMemoryPlan, StreamedPlan
from repro.service import BuildParams, TensorRegistry
from repro.store import (DiskStreamedPlan, StoreCorruptionError,
                         StoreFormatError, open_blco, save_blco)


def _factors(dims, rank=6, seed=0, dtype=np.float32):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal((d, rank)).astype(dtype))
            for d in dims]


def _rel_err(a, oracle):
    return np.max(np.abs(np.asarray(a, np.float64) - oracle)) / \
        (np.max(np.abs(oracle)) + 1e-30)


# ------------------------------------------------------------------ format
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_roundtrip_exact(tmp_path, dtype):
    """save -> open -> to_blco reproduces the split u64 hi/lo indices,
    values, blocks, and launches exactly — for f32 and f64 values."""
    t = core.random_tensor((25, 18, 21), 1500, seed=4, dtype=dtype)
    b = core.build_blco(t, max_nnz_per_block=128)
    path = str(tmp_path / "t.blco")
    nbytes = save_blco(b, path, fingerprint="fp", norm_x=2.5)
    assert nbytes == os.path.getsize(path)
    s = open_blco(path, verify=True)
    assert s.fingerprint == "fp" and s.norm_x == 2.5
    assert s.dims == b.dims and s.nnz == b.nnz
    assert s.re == b.re
    b2 = s.to_blco()
    np.testing.assert_array_equal(b2.idx_hi, b.idx_hi)
    np.testing.assert_array_equal(b2.idx_lo, b.idx_lo)
    np.testing.assert_array_equal(b2.values, b.values)
    assert b2.values.dtype == np.dtype(dtype)
    assert b2.blocks == b.blocks and b2.launches == b.launches
    assert b2.re == b.re and b2.spec == b.spec
    s.close()


def test_roundtrip_wide_index_uses_hi_word(tmp_path):
    """A >32-bit stored index exercises the hi uint32 word on disk."""
    t = core.random_tensor((1 << 13, 1 << 13, 1 << 13), 400, seed=7)
    b = core.build_blco(t)         # 39 index bits -> hi word nonzero
    assert int(b.idx_hi.max()) > 0
    path = str(tmp_path / "wide.blco")
    save_blco(b, path)
    b2 = open_blco(path, verify=True).to_blco()
    np.testing.assert_array_equal(b2.idx_hi, b.idx_hi)
    np.testing.assert_array_equal(b2.idx_lo, b.idx_lo)


def test_ragged_reservation_roundtrip(tmp_path):
    """An explicit non-pow2 reservation is honoured on disk and on read."""
    t = core.random_tensor((20, 16, 12), 3000, seed=1)
    b = core.build_blco(t, max_nnz_per_block=128)
    max_launch = max(l.nnz for l in b.launches)
    res = max_launch + 3                    # deliberately ragged
    path = str(tmp_path / "ragged.blco")
    save_blco(b, path, reservation_nnz=res)
    s = open_blco(path, verify=True)
    assert s.reservation_nnz == res
    hi, lo, vals, bases, n = s.chunk(0)
    assert hi.shape == (res,) and bases.shape == (res, t.order)
    factors = _factors(t.dims)
    plan = DiskStreamedPlan(s, queues=2)
    oracle = core.mttkrp_dense_oracle(t, factors, 1)
    assert _rel_err(plan.mttkrp(factors, 1), oracle) < 1e-3
    plan.close()


def test_open_rejects_non_store_and_bad_version(tmp_path):
    path = str(tmp_path / "junk.blco")
    with open(path, "wb") as f:
        f.write(b"NOTASTORE" + b"\0" * 64)
    with pytest.raises(StoreFormatError, match="not a BLCO store"):
        open_blco(path)
    # valid file, wrong version
    t = core.random_tensor((8, 7, 6), 50, seed=0)
    good = str(tmp_path / "good.blco")
    save_blco(core.build_blco(t), good)
    raw = bytearray(open(good, "rb").read())
    raw[8:12] = (99).to_bytes(4, "little")
    bad = str(tmp_path / "badver.blco")
    open(bad, "wb").write(bytes(raw))
    with pytest.raises(StoreFormatError, match="version 99"):
        open_blco(bad)


def test_truncated_file_detected_without_verify(tmp_path):
    t = core.random_tensor((20, 16, 12), 800, seed=2)
    path = str(tmp_path / "t.blco")
    save_blco(core.build_blco(t, max_nnz_per_block=128), path)
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size - 1)
    with pytest.raises(StoreCorruptionError, match="past end of file"):
        open_blco(path)        # bounds check runs even with verify=False


def test_corrupted_section_detected_by_checksum(tmp_path):
    t = core.random_tensor((20, 16, 12), 800, seed=3)
    b = core.build_blco(t, max_nnz_per_block=128)
    path = str(tmp_path / "t.blco")
    save_blco(b, path)
    s = open_blco(path)                     # find a real data byte to flip
    sec = s._header["sections"]["vals"]
    s.close()
    with open(path, "r+b") as f:
        f.seek(sec["offset"] + 5)
        byte = f.read(1)
        f.seek(sec["offset"] + 5)
        f.write(bytes([byte[0] ^ 0xFF]))
    with pytest.raises(StoreCorruptionError, match="checksum mismatch"):
        open_blco(path, verify=True)
    # header corruption is caught even without verify
    with open(path, "r+b") as f:
        f.seek(25)
        f.write(b"\xff")
    with pytest.raises(StoreCorruptionError):
        open_blco(path)


def test_empty_tensor_roundtrip(tmp_path):
    t = core.from_coo(np.zeros((0, 3), np.int64), np.zeros((0,), np.float32),
                      (8, 6, 4))
    b = core.build_blco(t)
    path = str(tmp_path / "empty.blco")
    save_blco(b, path)
    s = open_blco(path, verify=True)
    assert s.num_launches == 0 and s.to_blco().nnz == 0
    plan = DiskStreamedPlan(s)
    out = np.asarray(plan.mttkrp(_factors(t.dims, 5), 0))
    assert out.shape == (8, 5)
    np.testing.assert_array_equal(out, 0.0)
    plan.close()


# -------------------------------------------------------- disk-streamed plan
def test_disk_streamed_matches_in_memory_bitwise(tmp_path):
    """Acceptance: DiskStreamedPlan output == InMemoryPlan output on every
    mode and both conflict resolutions.  The streamed launches and the
    in-memory scan are compiled apart, so XLA may associate their sums
    differently: equal to f32 rounding (1e-6 of the largest output, about
    8 ulp), not bit for bit."""
    t = core.random_tensor((40, 25, 30), 2500, seed=5)
    b = core.build_blco(t, max_nnz_per_block=256)
    path = str(tmp_path / "t.blco")
    save_blco(b, path)
    disk = DiskStreamedPlan(path, queues=3)
    mem = plan_for(b, 1 << 40, rank=6, backend="in_memory")
    factors = _factors(t.dims)
    for mode in range(t.order):
        for res in ("register", "direct"):
            want = np.asarray(mem.mttkrp(factors, mode, resolution=res))
            np.testing.assert_allclose(
                np.asarray(disk.mttkrp(factors, mode, resolution=res)),
                want, rtol=0, atol=1e-6 * np.max(np.abs(want)),
                err_msg=f"mode {mode} res {res}")
        oracle = core.mttkrp_dense_oracle(t, factors, mode)
        assert _rel_err(disk.mttkrp(factors, mode), oracle) < 1e-3
    s = disk.stats()
    assert s.backend == "disk_streamed"
    assert s.disk_bytes == s.h2d_bytes > 0 and s.launches > 0
    freed = disk.close()
    assert freed == disk.spec.bytes_in_flight(3)
    assert disk.device_bytes() == 0
    mem.close()


def test_disk_streamed_holds_bounded_host_window(tmp_path):
    """Acceptance: at most ``queues`` reservation chunks of padded host
    memory are alive at any point while disk-streaming (tracked via
    weakref finalizers on every chunk the plan pulls)."""
    t = core.random_tensor((30, 22, 26), 4000, seed=6)
    b = core.build_blco(t, max_nnz_per_block=128)
    path = str(tmp_path / "t.blco")
    save_blco(b, path)
    stored = open_blco(path)
    queues = 3
    plan = DiskStreamedPlan(stored, queues=queues)
    assert plan.host_window_bytes() == queues * plan.spec.bytes_per_launch

    live = {"now": 0, "peak": 0, "total": 0}
    real_chunk = stored.chunk

    def tracking_chunk(i):
        out = real_chunk(i)
        arr = np.array(out[0])          # a per-chunk allocation we can track
        live["now"] += 1
        live["total"] += 1
        live["peak"] = max(live["peak"], live["now"])

        def _dead(_ref=None):
            live["now"] -= 1
        weakref.finalize(arr, _dead)
        return (arr,) + out[1:]

    stored.chunk = tracking_chunk
    plan.mttkrp(_factors(t.dims), 0)
    n_launches = len(b.launches)
    assert n_launches > 2 * queues       # the test only means something then
    assert live["total"] == n_launches
    # the streaming loop keeps <= queues transfers in flight; allow the one
    # chunk being issued on top of the full window
    assert live["peak"] <= queues + 1, live
    plan.close()


def test_plan_for_disk_regime_and_host_budget(tmp_path):
    """plan_for picks the disk tier when the tensor exceeds the host
    budget, honours backend="disk_streamed", and cleans up temp spills."""
    t = core.random_tensor((30, 22, 26), 2000, seed=8)
    b = core.build_blco(t, max_nnz_per_block=256)
    factors = _factors(t.dims)

    # auto: host budget below the tensor's host footprint -> disk tier
    plan = plan_for(b, 1 << 40, rank=6,
                    host_budget_bytes=core.format_bytes(b) - 1)
    assert isinstance(plan, DiskStreamedPlan)
    temp_file = plan.stored.path
    assert os.path.exists(temp_file)
    oracle = core.mttkrp_dense_oracle(t, factors, 0)
    assert _rel_err(plan.mttkrp(factors, 0), oracle) < 1e-3
    plan.close()
    assert not os.path.exists(temp_file)    # anonymous spill is cleaned up

    # auto with a generous host budget stays in memory
    assert isinstance(plan_for(b, 1 << 40, rank=6,
                               host_budget_bytes=1 << 40), InMemoryPlan)

    # explicit backend + explicit store path -> file is kept
    keep = str(tmp_path / "kept.blco")
    plan = plan_for(b, 1 << 40, rank=6, backend="disk_streamed",
                    store_path=keep)
    plan.mttkrp(factors, 1)
    plan.close()
    assert os.path.exists(keep)

    # device budget still binds: reservation + factors must fit
    with pytest.raises(ValueError, match="disk-streamed plan needs"):
        plan_for(b, 1, rank=6, backend="disk_streamed")


# ------------------------------------------------- lazy host streaming window
def test_streamed_plan_pads_lazily_bounded_window():
    """Regression (eager host blow-up): StreamedPlan must not materialize
    every padded launch at construction — padding happens per chunk inside
    the streaming loop, and at most queues+1 padded chunks are alive."""
    t = core.random_tensor((30, 22, 26), 4000, seed=9)
    b = core.build_blco(t, max_nnz_per_block=128)
    queues = 3
    plan = StreamedPlan(b, queues=queues)
    chunks = plan._chunks
    assert isinstance(chunks, LaunchChunks)
    assert chunks.pads == 0                 # nothing padded at construction
    assert plan.host_window_bytes() == queues * plan.spec.bytes_per_launch

    live = {"now": 0, "peak": 0}
    real_chunk = chunks.chunk

    def tracking_chunk(i):
        out = real_chunk(i)
        live["now"] += 1
        live["peak"] = max(live["peak"], live["now"])

        def _dead(_ref=None):
            live["now"] -= 1
        weakref.finalize(out[0], _dead)
        return out

    chunks.chunk = tracking_chunk
    plan.mttkrp(_factors(t.dims), 0)
    n_launches = len(b.launches)
    assert n_launches > 2 * queues
    assert chunks.pads == n_launches        # one pass pads each launch once
    assert live["peak"] <= queues + 1, live
    plan.mttkrp(_factors(t.dims), 1)        # re-iterable across calls
    assert chunks.pads == 2 * n_launches
    plan.close()


def test_oom_executor_pads_lazily():
    t = core.random_tensor((25, 18, 21), 1200, seed=4)
    b = core.build_blco(t, max_nnz_per_block=128)
    ex = core.OOMExecutor(b, queues=2)
    assert isinstance(ex._prepared, LaunchChunks)
    assert ex._prepared.pads == 0
    ex.mttkrp(_factors(t.dims, 4), 0)
    assert ex._prepared.pads == len(b.launches)


# ------------------------------------------------------- registry spill tier
def _registry_tensor(seed=0, nnz=900):
    return core.random_tensor((30, 22, 26), nnz, seed=seed)


def test_registry_spill_load_roundtrip(tmp_path):
    reg = TensorRegistry(store_dir=str(tmp_path))
    build = BuildParams(max_nnz_per_block=256)
    t = _registry_tensor()
    h = reg.register(t, build=build)
    hb = reg.host_bytes()
    assert hb == h.host_bytes > 0
    blco_before = h.blco

    freed = reg.spill(h.key)
    assert freed == hb and not h.resident and h.chunks is None
    assert reg.host_bytes() == 0 and reg.store_bytes() > 0
    assert reg.spill(h.key) == 0            # idempotent

    reg.load(h.key)
    assert h.resident and reg.host_bytes() == hb and reg.loads == 1
    np.testing.assert_array_equal(h.blco.idx_hi, blco_before.idx_hi)
    np.testing.assert_array_equal(h.blco.values, blco_before.values)
    assert h.blco.launches == blco_before.launches


def test_registry_spill_refuses_pinned(tmp_path):
    reg = TensorRegistry(store_dir=str(tmp_path))
    h = reg.register(_registry_tensor(), build=BuildParams(max_nnz_per_block=256))
    h.pin()
    with pytest.raises(RuntimeError, match="pinned"):
        reg.spill(h.key)
    h.unpin()
    assert reg.spill(h.key) > 0


def test_registry_lru_spills_over_host_budget(tmp_path):
    """Satellite: automatic LRU eviction over host_bytes() — least
    recently used unpinned handle spills to the store, pinned handles
    survive even over budget."""
    build = BuildParams(max_nnz_per_block=256)
    tensors = [_registry_tensor(seed=i, nnz=900) for i in range(3)]
    probe = TensorRegistry()
    sizes = [probe.register(t, build=build).host_bytes for t in tensors]
    budget = sizes[0] + sizes[1] + sizes[2] // 2     # fits two, not three

    reg = TensorRegistry(store_dir=str(tmp_path), host_budget_bytes=budget)
    h0 = reg.register(tensors[0], build=build)
    h1 = reg.register(tensors[1], build=build)
    assert reg.host_bytes() <= budget and reg.spills == 0
    reg.get(h0.key)                          # h1 becomes least recently used
    h2 = reg.register(tensors[2], build=build)
    assert reg.spills == 1
    assert not h1.resident and h0.resident and h2.resident   # LRU spilled h1
    assert reg.host_bytes() <= budget

    # spilled entries stay registered: a re-register is a (disk) hit
    misses = reg.misses
    assert reg.register(tensors[1], build=build) is h1
    assert reg.misses == misses

    # pinned handles are never spilled, even over budget
    h0.pin(); h2.pin()
    reg.load(h1.key)                         # load pushes us over budget
    assert reg.host_bytes() > budget or not h1.resident
    assert h0.resident and h2.resident
    h0.unpin(); h2.unpin()


def test_registry_restart_reuses_fingerprint_no_rebuild(tmp_path):
    """Acceptance: a spilled-then-reloaded entry reuses its fingerprint
    (no BLCO rebuild) across a simulated process restart."""
    build = BuildParams(max_nnz_per_block=256)
    t = _registry_tensor(seed=3)
    reg1 = TensorRegistry(store_dir=str(tmp_path))
    h1 = reg1.register(t, build=build)
    reg1.spill(h1.key)
    assert reg1.misses == 1

    # "restart": a brand-new registry over the same store directory
    reg2 = TensorRegistry(store_dir=str(tmp_path))
    h2 = reg2.register(t, build=build)
    assert h2.key == h1.key
    assert reg2.misses == 0 and reg2.disk_hits == 1 and reg2.hits == 1
    assert not h2.resident and h2.store_path == h1.store_path
    assert h2.dims == t.dims and h2.nnz == t.nnz
    assert h2.norm_x == pytest.approx(h1.norm_x)
    # the reloaded BLCO is bit-identical to the original build
    reg2.load(h2.key)
    reg1.load(h1.key)
    np.testing.assert_array_equal(h2.blco.idx_hi, h1.blco.idx_hi)
    np.testing.assert_array_equal(h2.blco.idx_lo, h1.blco.idx_lo)
    np.testing.assert_array_equal(h2.blco.values, h1.blco.values)
    assert h2.blco.launches == h1.blco.launches


def test_registry_load_is_not_immediately_respilled(tmp_path):
    """Regression: load() of a tensor bigger than the whole host budget
    must return a RESIDENT handle (and count one load, not a spill/load
    churn) — an explicit reload is exempt from its own eviction pass."""
    build = BuildParams(max_nnz_per_block=256)
    t = _registry_tensor(seed=5)
    probe = TensorRegistry()
    size = probe.register(t, build=build).host_bytes

    reg = TensorRegistry(store_dir=str(tmp_path), host_budget_bytes=size // 2)
    h = reg.register(t, build=build)
    assert not h.resident and reg.spills == 1    # auto-spilled over budget
    reg.load(h.key)
    assert h.resident                            # NOT spilled straight back
    assert reg.loads == 1 and reg.spills == 1    # no churn, no double count
    assert reg.host_bytes() > reg.host_budget_bytes   # over budget, like pins
    # but a later registration still evicts it normally (it is plain LRU)
    reg.register(_registry_tensor(seed=6), build=build)
    assert not h.resident and reg.spills >= 2


def test_register_falls_back_to_rebuild_on_corrupt_store_file(tmp_path):
    """Regression: a damaged <fingerprint>.blco (crash mid-write, bit rot)
    must not brick registration while the COO is in hand — register()
    falls back to a rebuild, and the next spill repairs the disk tier."""
    build = BuildParams(max_nnz_per_block=256)
    t = _registry_tensor(seed=7)
    reg1 = TensorRegistry(store_dir=str(tmp_path))
    h1 = reg1.register(t, build=build)
    reg1.spill(h1.key)
    with open(h1.store_path, "r+b") as f:       # damage the store file
        f.truncate(os.path.getsize(h1.store_path) // 2)

    reg2 = TensorRegistry(store_dir=str(tmp_path))
    h2 = reg2.register(t, build=build)          # must not raise
    assert h2.resident and reg2.misses == 1 and reg2.disk_hits == 0
    assert reg2.spill(h2.key) > 0               # re-persist over the damage
    reg3 = TensorRegistry(store_dir=str(tmp_path))
    assert not reg3.register(t, build=build).resident
    assert reg3.disk_hits == 1                  # disk tier repaired

    # data-only corruption (valid header, bad section bytes) must ALSO be
    # caught at adoption — silently streaming bit-rotted values would be
    # worse than the rebuild
    path = reg3.get(h1.key).store_path
    s = open_blco(path)
    off = s._header["sections"]["vals"]["offset"]
    s.close()
    with open(path, "r+b") as f:
        f.seek(off + 3)
        byte = f.read(1)
        f.seek(off + 3)
        f.write(bytes([byte[0] ^ 0xFF]))
    reg4 = TensorRegistry(store_dir=str(tmp_path))
    h4 = reg4.register(t, build=build)          # rebuild, not garbage
    assert h4.resident and reg4.misses == 1 and reg4.disk_hits == 0


def test_save_blco_is_atomic(tmp_path, monkeypatch):
    """save_blco commits via rename: no .tmp remnants on success, and a
    mid-write failure leaves nothing at the final path."""
    import repro.store.format as fmt
    t = core.random_tensor((20, 16, 12), 800, seed=2)
    b = core.build_blco(t, max_nnz_per_block=128)
    path = str(tmp_path / "t.blco")
    save_blco(b, path)
    assert os.path.exists(path) and not os.path.exists(path + ".tmp")

    # fail partway through the data pass: neither the final path nor the
    # temp file may survive (a crashed persist must not brick adoption)
    class Boom(fmt.LaunchChunks):
        def chunk(self, i):
            if i >= 2:
                raise RuntimeError("simulated crash mid-write")
            return super().chunk(i)

    monkeypatch.setattr(fmt, "LaunchChunks", Boom)
    bad = str(tmp_path / "bad.blco")
    with pytest.raises(RuntimeError, match="mid-write"):
        save_blco(b, bad)
    assert not os.path.exists(bad) and not os.path.exists(bad + ".tmp")


def test_service_reloads_spilled_tensor_when_host_has_room(tmp_path):
    """Submit-path tier policy: an adopted/spilled tensor is reloaded to
    the host (regaining the in-memory fast path) when the host budget has
    room, and disk-streams only under genuine host pressure."""
    from repro.service import DecompositionService, SubmitDecomposition
    build = BuildParams(max_nnz_per_block=256)
    t = _registry_tensor()
    seed_reg = TensorRegistry(store_dir=str(tmp_path))
    h = seed_reg.register(t, build=build)
    size = h.host_bytes
    seed_reg.spill(h.key)                     # the store file a restart sees

    roomy = DecompositionService(device_budget_bytes=64 << 20,
                                 store_dir=str(tmp_path))
    jid = roomy.submit(SubmitDecomposition(tensor=t, rank=4, iters=1,
                                           tol=0.0, build=build))
    assert roomy.status(jid).backend == "in_memory"   # reloaded off disk
    assert roomy.registry.misses == 0                 # ... without a rebuild

    pressed = DecompositionService(device_budget_bytes=64 << 20,
                                   store_dir=str(tmp_path),
                                   host_budget_bytes=size // 2)
    jid2 = pressed.submit(SubmitDecomposition(tensor=t, rank=4, iters=1,
                                              tol=0.0, build=build))
    assert pressed.status(jid2).backend == "disk_streamed"  # stub stays
    roomy.run(); pressed.run()
    assert roomy.status(jid).state == pressed.status(jid2).state == "done"


def test_registry_without_store_dir_cannot_spill():
    reg = TensorRegistry()
    h = reg.register(_registry_tensor(), build=BuildParams(max_nnz_per_block=256))
    with pytest.raises(RuntimeError, match="store_dir"):
        reg.spill(h.key)
