"""FLYCOO format invariants (paper §III) — including the PR-8
``repro.reorder`` extension: ``build_flycoo(ordering=...)`` /
``pack_mode`` locality sorting and ``build_block_layout``'s
``order_keys`` path keep every layout contract intact."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.flycoo import build_flycoo, choose_partition_params, pack_mode
from repro.core.tensors import frostt_like, random_sparse_tensor
from repro.kernels.mttkrp import ops as kops
from repro.reorder.ordering import ORDERINGS, locality_keys


def small_tensor(seed=0, nnz=300):
    return random_sparse_tensor((40, 30, 20), nnz, seed=seed,
                                distribution="powerlaw")


def test_partition_covers_every_nonzero_once_per_mode():
    t = small_tensor()
    ft = build_flycoo(t, 4, m_bounds=(4, 16), g_bounds=(8, 64))
    for n in range(t.nmodes):
        owner = ft.owner_of(n)
        assert owner.shape == (t.nnz,)
        assert owner.min() >= 0 and owner.max() < 4
        # owners come from the super-shard of the output index
        mp = ft.modes[n]
        expect = mp.super_to_device[t.indices[:, n] // mp.m]
        assert np.array_equal(owner, expect)


def test_row_perm_is_permutation_and_device_major():
    t = small_tensor()
    ft = build_flycoo(t, 4, m_bounds=(4, 16), g_bounds=(8, 64))
    for mp in ft.modes:
        dim = t.shape[mp.mode]
        assert sorted(mp.row_perm.tolist()) == sorted(
            set(mp.row_perm.tolist()))
        # round trip
        assert np.array_equal(mp.row_unperm[mp.row_perm], np.arange(dim))
        # device-major: each row's slot // rows_cap == its owner device
        owner_of_row = mp.super_to_device[np.arange(dim) // mp.m]
        assert np.array_equal(mp.row_perm // mp.rows_cap, owner_of_row)


def test_pack_mode_sorted_and_complete():
    t = small_tensor()
    ft = build_flycoo(t, 4, m_bounds=(4, 16), g_bounds=(8, 64))
    for n in range(t.nmodes):
        idx, val, mask = pack_mode(ft, n)
        assert mask.sum() == t.nnz
        assert abs(val[mask].sum() - t.values.sum()) < 1e-3
        for d in range(4):
            rows = idx[d, mask[d], n]
            assert np.all(np.diff(rows) >= 0)          # sorted by output row
            assert np.all(rows // ft.modes[n].rows_cap == d)   # owned


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000))
def test_partition_params_satisfy_eq2(seed):
    rng = np.random.default_rng(seed)
    shape = tuple(int(rng.integers(8, 5000)) for _ in range(3))
    p = choose_partition_params(shape, nnz=100_000, num_workers=8)
    for dim, m in zip(shape, p.m):
        k = -(-dim // m)
        # Eq.2: super-shard count ≥ workers (divisible up to the ragged tail)
        assert k >= 1
        if dim > 8:
            assert k >= 8 or m == 1


def _block_layout_invariants(row, valid, idx_in, ordering, *, rows_cap,
                             blk, tile_rows):
    """build_block_layout contract, with and without locality keys."""
    n_el = len(row)
    n_pad = kops.n_pad_for(n_el, rows_cap, blk, tile_rows)
    keys = locality_keys(idx_in, ordering)
    slot, tile_of_block = kops.build_block_layout(
        jnp.asarray(row), jnp.asarray(valid), rows_cap=rows_cap, blk=blk,
        tile_rows=tile_rows, order_keys=keys or None)
    slot = np.asarray(slot)
    tile_of_block = np.asarray(tile_of_block)

    # invalid -> dump slot; valid -> injective in-range slots
    assert np.all(slot[~valid] == n_pad)
    vslots = slot[valid]
    assert np.all((0 <= vslots) & (vslots < n_pad))
    assert len(np.unique(vslots)) == len(vslots)
    # each element's block is attributed to exactly its own output tile —
    # locality keys reorder *within* a tile, never across
    vtile = row[valid] // tile_rows
    assert np.array_equal(tile_of_block[vslots // blk], vtile)
    assert np.all(np.diff(tile_of_block) >= 0)
    # tile_of_block is independent of the ordering policy (same nonzeros
    # per tile, so the same block counts)
    base_slot, base_tiles = kops.build_block_layout(
        jnp.asarray(row), jnp.asarray(valid), rows_cap=rows_cap, blk=blk,
        tile_rows=tile_rows)
    assert np.array_equal(tile_of_block, np.asarray(base_tiles))
    # within a tile, slot order realizes the locality keys (ascending
    # lexicographically, most significant first)
    if keys:
        key_mat = np.stack([np.asarray(kk) for kk in keys], axis=1)
        for t in np.unique(vtile):
            sel = vtile == t
            run = key_mat[valid][sel][np.argsort(vslots[sel])]
            for prev, cur in zip(run, run[1:]):
                assert tuple(prev) <= tuple(cur)
    return slot, tile_of_block


@pytest.mark.parametrize("ordering", ORDERINGS)
def test_build_block_layout_order_keys_invariants(ordering):
    rng = np.random.default_rng(3)
    tiles, tile_rows, blk, n_el = 5, 8, 16, 230
    rows_cap = tiles * tile_rows
    row = np.sort(rng.integers(0, rows_cap, n_el)).astype(np.int32)
    valid = np.ones(n_el, bool)
    valid[-11:] = False
    idx_in = rng.integers(0, 4000, size=(n_el, 2)).astype(np.int32)
    idx_in[~valid] = 0
    _block_layout_invariants(row, valid, idx_in, ordering,
                             rows_cap=rows_cap, blk=blk,
                             tile_rows=tile_rows)


@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    n_el=st.integers(1, 300),
    tiles=st.integers(1, 6),
    tile_rows=st.sampled_from([8, 16]),
    blk=st.sampled_from([8, 16, 32]),
    k=st.integers(1, 4),
    ordering=st.sampled_from(ORDERINGS),
    frac_invalid=st.floats(0.0, 0.4),
)
def test_build_block_layout_order_keys_property(seed, n_el, tiles,
                                                tile_rows, blk, k, ordering,
                                                frac_invalid):
    rows_cap = tiles * tile_rows
    rng = np.random.default_rng(seed)
    row = np.sort(rng.integers(0, rows_cap, n_el)).astype(np.int32)
    valid = np.ones(n_el, bool)
    ninv = int(n_el * frac_invalid)
    if ninv:
        valid[-ninv:] = False
    idx_in = rng.integers(0, 10_000, size=(n_el, k)).astype(np.int32)
    idx_in[~valid] = 0
    _block_layout_invariants(row, valid, idx_in, ordering,
                             rows_cap=rows_cap, blk=blk,
                             tile_rows=tile_rows)


@pytest.mark.parametrize("ordering", ["tile", "morton"])
def test_pack_mode_with_ordering_keeps_contract(ordering):
    """A reorder policy on the FLYCOO tensor must not disturb anything
    pack_mode guarantees: same multiset per device, rows still sorted
    and owned — the locality keys only break ties within an output row."""
    t = small_tensor()
    base = build_flycoo(t, 4, m_bounds=(4, 16), g_bounds=(8, 64))
    ft = build_flycoo(t, 4, m_bounds=(4, 16), g_bounds=(8, 64),
                      ordering=ordering)
    assert ft.ordering == ordering
    for n in range(t.nmodes):
        idx0, val0, mask0 = pack_mode(base, n)
        idx, val, mask = pack_mode(ft, n)
        assert mask.sum() == t.nnz
        for d in range(4):
            rows = idx[d, mask[d], n]
            assert np.all(np.diff(rows) >= 0)          # still row-sorted
            assert np.all(rows // ft.modes[n].rows_cap == d)   # owned
            # same nonzeros per device as the unordered pack (a
            # permutation within the device's slice)
            assert np.array_equal(np.sort(val[d, mask[d]]),
                                  np.sort(val0[d, mask0[d]]))
            order0 = np.lexsort(idx0[d, mask0[d]].T)
            order1 = np.lexsort(idx[d, mask[d]].T)
            assert np.array_equal(idx0[d, mask0[d]][order0],
                                  idx[d, mask[d]][order1])


def test_build_flycoo_rejects_unknown_ordering():
    with pytest.raises(ValueError, match="unknown ordering"):
        build_flycoo(small_tensor(), 4, ordering="hilbert")


def test_frostt_profiles_build():
    for name in ("nell-2", "vast"):
        t = frostt_like(name, scale=0.02)
        ft = build_flycoo(t, 4)
        assert ft.nnz == t.nnz
        assert ft.params.g >= 1


# ---------------------------------------------------------------------------
# Input validation (PR-9): reject malformed tensors before partitioning
# ---------------------------------------------------------------------------

from repro.core.tensors import SparseTensor


def _tensor(indices, values, shape=(8, 6, 5)):
    return SparseTensor(np.asarray(indices, np.int64).reshape(-1, len(shape)),
                        np.asarray(values, np.float32), shape)


def test_build_flycoo_empty_tensor():
    t = SparseTensor(np.zeros((0, 3), np.int64), np.zeros((0,), np.float32),
                     (8, 6, 5))
    ft = build_flycoo(t, 2)
    assert ft.nnz == 0
    for n in range(3):
        idx, val, mask = pack_mode(ft, n)
        assert mask.sum() == 0


def test_build_flycoo_single_nonzero():
    t = _tensor([[3, 2, 1]], [2.5])
    ft = build_flycoo(t, 2)
    assert ft.nnz == 1
    for n in range(3):
        idx, val, mask = pack_mode(ft, n)
        assert mask.sum() == 1
        assert val[mask][0] == np.float32(2.5)


def test_build_flycoo_max_index_boundary():
    # index == dim-1 in every mode is legal; == dim is not.
    ok = _tensor([[7, 5, 4], [0, 0, 0]], [1.0, 2.0])
    assert build_flycoo(ok, 2).nnz == 2
    bad = _tensor([[7, 6, 4]], [1.0])
    with pytest.raises(ValueError, match=r"mode-1 index out of range"):
        build_flycoo(bad, 2)


def test_build_flycoo_rejects_negative_index():
    with pytest.raises(ValueError, match=r"mode-2 index out of range"):
        build_flycoo(_tensor([[1, 1, -1]], [1.0]), 2)


def test_build_flycoo_rejects_nonfinite_value_naming_offender():
    t = _tensor([[1, 1, 1], [2, 2, 2], [3, 3, 3]],
                [1.0, np.nan, np.inf])
    with pytest.raises(ValueError, match=r"non-finite value at nonzero 1"):
        build_flycoo(t, 2)


def test_validate_tensor_rejects_shape_mismatches():
    # SparseTensor's own asserts catch these at construction, so drive
    # the validator directly with duck-typed stand-ins.
    from repro.core.flycoo import _validate_tensor

    class BadIdx:
        indices = np.zeros((4, 2), np.int64)     # 2 cols for a 3-mode shape
        values = np.zeros((4,), np.float32)
        shape = (8, 6, 5)

    class BadVal:
        indices = np.zeros((4, 3), np.int64)
        values = np.zeros((3,), np.float32)
        shape = (8, 6, 5)

    with pytest.raises(ValueError, match="indices must be"):
        _validate_tensor(BadIdx())
    with pytest.raises(ValueError, match="values must be"):
        _validate_tensor(BadVal())


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_build_flycoo_adversarial_corruption(seed):
    """Any single corrupted nonzero (index out of range either side, or
    non-finite value) is rejected with a ValueError — never a silently
    wrong partition."""
    rng = np.random.default_rng(seed)
    shape = tuple(int(rng.integers(2, 12)) for _ in range(3))
    nnz = int(rng.integers(1, 40))
    idx = np.stack([rng.integers(0, d, size=nnz) for d in shape],
                   axis=1).astype(np.int64)
    val = rng.standard_normal(nnz).astype(np.float32)
    victim = int(rng.integers(0, nnz))
    mode = int(rng.integers(0, 3))
    attack = rng.choice(["high", "neg", "nan", "inf"])
    if attack == "high":
        idx[victim, mode] = shape[mode] + int(rng.integers(0, 1000))
    elif attack == "neg":
        idx[victim, mode] = -1 - int(rng.integers(0, 1000))
    elif attack == "nan":
        val[victim] = np.nan
    else:
        val[victim] = np.inf
    with pytest.raises(ValueError):
        build_flycoo(SparseTensor(idx, val, shape), 2)


@pytest.mark.parametrize("num_workers", [2, 4, 8])
def test_partition_params_eq2_every_small_width(num_workers):
    """Every width the Hypothesis test can draw near ν, checked in full:
    at least ν super-shards wherever the width exceeds ν."""
    for dim in range(num_workers + 1, 400):
        (m,) = choose_partition_params((dim,), nnz=100_000,
                                       num_workers=num_workers).m
        assert -(-dim // m) >= num_workers, (dim, m)


def test_partition_params_keep_nell2_x4_intervals():
    """The partitioner's choice for nell-2 on four workers is frozen in
    the benchmark's nell-2-x4 configuration (its ``intervals``)."""
    p = choose_partition_params((12092, 9184, 28818), nnz=16_000_000,
                                num_workers=4)
    assert p.m == (1008, 1148, 1802)
