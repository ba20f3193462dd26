"""Property tests for ALTO linearization + BLCO re-encoding/blocking."""
import numpy as np
import pytest

from conftest import hypothesis_or_stub

from repro.core import linearize as lin

given, settings, st = hypothesis_or_stub()
from repro.core import tensor as tz
from repro.core.blco import build_blco
from repro.core.u64 import join64, split64

dims_strategy = st.lists(st.integers(2, 300), min_size=2, max_size=5)


@settings(max_examples=30, deadline=None)
@given(dims=dims_strategy, seed=st.integers(0, 2**31 - 1))
def test_alto_roundtrip(dims, seed):
    spec = lin.LinearSpec.make(dims)
    rng = np.random.default_rng(seed)
    idx = np.stack([rng.integers(0, d, 64) for d in dims], 1).astype(np.int64)
    hi, lo = lin.alto_encode(spec, idx)
    back = lin.alto_decode(spec, hi, lo)
    np.testing.assert_array_equal(back, idx)


@settings(max_examples=30, deadline=None)
@given(dims=dims_strategy, seed=st.integers(0, 2**31 - 1),
       target=st.sampled_from([8, 12, 16, 64]))
def test_reencode_roundtrip_with_blocking(dims, seed, target):
    spec = lin.LinearSpec.make(dims)
    re = lin.reencode_spec(spec, target)
    rng = np.random.default_rng(seed)
    idx = np.stack([rng.integers(0, d, 64) for d in dims], 1).astype(np.int64)
    hi, lo = lin.alto_encode(spec, idx)
    keys = lin.block_key(spec, re, hi, lo)
    stored = lin.reencode(spec, re, idx)
    for key in np.unique(keys):
        sel = keys == key
        upper = lin.key_to_upper_coords(spec, re, int(key))
        back = lin.delinearize_host(re, stored[sel], upper)
        np.testing.assert_array_equal(back, idx[sel])


def test_alto_positions_cover_all_bits():
    for dims in [(5, 5), (1000, 3, 17), (2, 2, 2, 2, 900)]:
        spec = lin.LinearSpec.make(dims)
        flat = sorted(p for ps in spec.positions for p in ps)
        assert flat == list(range(spec.total_bits))
        for n, d in enumerate(dims):
            assert 2 ** spec.bits[n] >= d


def test_alto_ordering_is_morton_for_regular_dims():
    # equal mode lengths -> round-robin == Morton-Z interleave
    spec = lin.LinearSpec.make((4, 4, 4))
    assert spec.positions == ((0, 3), (1, 4), (2, 5))


@pytest.mark.parametrize("target_bits,max_nnz", [(6, 16), (10, 64), (64, 1 << 20)])
def test_blocking_invariants(target_bits, max_nnz):
    t = tz.random_tensor((37, 11, 53, 7), 3000, seed=0, dist="powerlaw")
    b = build_blco(t, target_bits=target_bits, max_nnz_per_block=max_nnz)
    # partition: blocks tile [0, nnz) exactly
    assert b.blocks[0].start == 0
    assert b.blocks[-1].end == b.nnz
    for prev, cur in zip(b.blocks, b.blocks[1:]):
        assert prev.end == cur.start
    # size budget
    assert all(blk.nnz <= max_nnz for blk in b.blocks)
    # in-block stored index fits target bits
    stored = join64(b.idx_hi, b.idx_lo)
    assert b.re.inblock_bits <= target_bits
    if b.re.inblock_bits < 64:
        assert int(stored.max()) < (1 << b.re.inblock_bits)
    # launches tile the block list exactly
    ids = [i for l in b.launches for i in l.block_ids]
    assert ids == list(range(len(b.blocks)))
    # every element delinearizes to its original coordinate set (as multiset)
    total = sum(blk.nnz for blk in b.blocks)
    assert total == t.nnz


def test_construction_stats_recorded():
    t = tz.random_tensor((64, 64, 64), 1000, seed=1)
    b = build_blco(t)
    for k in ("linearize", "sort", "block_keys", "reencode", "blocking",
              "batching"):
        assert k in b.construction_stats


def test_tns_roundtrip(tmp_path):
    t = tz.random_tensor((9, 8, 7), 50, seed=2, dtype=np.float64)
    p = tmp_path / "x.tns"
    with open(p, "w") as f:
        for row, v in zip(t.indices, t.values):
            f.write(" ".join(str(i + 1) for i in row) + f" {v}\n")
    t2 = tz.load_tns(str(p))
    assert t2.nnz == t.nnz
    np.testing.assert_allclose(t2.to_dense(), t.to_dense(), rtol=1e-12)


@pytest.mark.parametrize("dist", ["powerlaw", "uniform"])
def test_random_tensor_exact_has_exactly_nnz_distinct(dist):
    """The exact generator reaches the requested nnz with distinct, sorted
    coordinates (power-law draws repeat, so it tops up), and the same seed
    gives the same tensor."""
    dims, nnz = (120, 90, 280), 60_000
    t = tz.random_tensor_exact(dims, nnz, seed=3, dist=dist)
    assert t.nnz == nnz and t.dims == dims
    keys = np.ravel_multi_index(t.indices.T, dims)
    assert np.all(np.diff(keys) > 0)                  # distinct, lex order
    assert np.all(t.values != 0)
    assert np.all(t.indices >= 0) and np.all(t.indices < np.array(dims))
    again = tz.random_tensor_exact(dims, nnz, seed=3, dist=dist)
    np.testing.assert_array_equal(again.indices, t.indices)
    np.testing.assert_array_equal(again.values, t.values)
    if dist == "powerlaw":
        # the same draws deduplicated by random_tensor fall short of nnz
        assert tz.random_tensor(dims, nnz, seed=3, dist=dist).nnz < nnz


def test_frostt_like_keeps_published_dims():
    dims, full, _ = tz.FROSTT_SUITE["nell-2"]
    assert dims == (12092, 9184, 28818) and full == 76_879_419
    t = tz.frostt_like("nell-2", seed=0, nnz=5_000)
    assert t.dims == dims and t.nnz == 5_000
