"""Pallas MTTKRP kernel: shape/dtype sweeps vs. the pure-jnp oracle
(interpret mode executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.mttkrp import ops as kops
from repro.kernels.mttkrp import ref as kref


def _case(seed, n_el, rows, rank, frac_invalid=0.05):
    rng = np.random.default_rng(seed)
    row = np.sort(rng.integers(0, rows, n_el)).astype(np.int32)
    contrib = rng.standard_normal((n_el, rank)).astype(np.float32)
    valid = np.ones(n_el, bool)
    k = int(n_el * frac_invalid)
    if k:
        valid[-k:] = False
        contrib[-k:] = 0.0
        row[-k:] = rows - 1
    return jnp.asarray(contrib), jnp.asarray(row), jnp.asarray(valid)


@pytest.mark.parametrize("n_el,rows,rank,blk,tile_rows", [
    (64, 16, 4, 16, 8),
    (333, 64, 8, 32, 8),
    (1000, 256, 16, 128, 128),
    (777, 128, 32, 64, 16),
    (2048, 512, 128, 512, 128),     # production-aligned tile
    (100, 8, 3, 32, 8),             # rank not MXU-aligned → padded
])
def test_segment_accumulate_matches_ref(n_el, rows, rank, blk, tile_rows):
    contrib, row, valid = _case(0, n_el, rows, rank)
    out = kops.mttkrp_blocked(contrib, row, valid, rows_cap=rows, blk=blk,
                              tile_rows=tile_rows, interpret=True)
    ref = kref.segment_accumulate_ref(
        jnp.where(valid[:, None], contrib, 0),
        jnp.where(valid, row, 0), rows)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_accumulate_dtypes(dtype):
    contrib, row, valid = _case(1, 500, 64, 16)
    contrib = contrib.astype(dtype)
    out = kops.mttkrp_blocked(contrib, row, valid, rows_cap=64, blk=64,
                              tile_rows=16, interpret=True)
    ref = kref.segment_accumulate_ref(
        jnp.where(valid[:, None], contrib, 0).astype(jnp.float32),
        jnp.where(valid, row, 0), 64)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=tol, atol=tol)


@pytest.mark.parametrize("seed", range(4))
def test_fused_3mode_matches_device_step_ref(seed):
    """Fused Hadamard+scatter kernel == generic ref path on real layouts."""
    rng = np.random.default_rng(seed)
    cap, rows_cap, rank, nmodes = 300, 32, 8, 3
    idx = np.stack([
        np.sort(rng.integers(0, rows_cap, cap)),          # output rows
        rng.integers(0, 64, cap),
        rng.integers(0, 48, cap),
    ], axis=1).astype(np.int32)
    val = rng.standard_normal(cap).astype(np.float32)
    valid = np.arange(cap) < cap - 11
    factors = [jnp.asarray(rng.standard_normal((n, rank)), jnp.float32)
               for n in (rows_cap, 64, 48)]
    kw = dict(mode=0, rows_cap=rows_cap, row_offset=0, blk=32, tile_rows=8,
              interpret=True)
    ref = kops.mttkrp_device_step(jnp.asarray(idx), jnp.asarray(val),
                                  jnp.asarray(valid), factors,
                                  backend="ref", **kw)
    for backend in ("pallas_fused", "pallas_fused_tiled"):
        got = kops.mttkrp_device_step(jnp.asarray(idx), jnp.asarray(val),
                                      jnp.asarray(valid), factors,
                                      backend=backend, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_build_block_layout_invariants():
    """Blocks never straddle an output row tile; slots are unique."""
    rng = np.random.default_rng(0)
    cap, rows_cap, blk, tile_rows = 500, 64, 32, 16
    row = np.sort(rng.integers(0, rows_cap, cap)).astype(np.int32)
    valid = np.ones(cap, bool)
    slot, tile_of_block = kops.build_block_layout(
        jnp.asarray(row), jnp.asarray(valid), rows_cap=rows_cap, blk=blk,
        tile_rows=tile_rows)
    slot = np.asarray(slot)
    assert len(np.unique(slot)) == cap            # injective
    blocks = slot // blk
    tob = np.asarray(tile_of_block)
    # every element's block is tagged with that element's tile
    np.testing.assert_array_equal(tob[blocks], row // tile_rows)


@pytest.mark.parametrize("rank,rows", [(1, 8), (8, 8), (16, 16), (20, 24),
                                       (128, 128), (129, 256), (300, 384)])
def test_krp_rows_rounds_to_sublanes_then_slabs(rank, rows):
    """The row-gathering rungs' operand: R to whole 8-row sublane tiles up
    to one 128-wide slab, whole slabs above it."""
    from repro.kernels.mttkrp import kernel as kkernel
    assert kkernel.krp_rows(rank) == rows


@pytest.mark.parametrize("chunk", [7, 16, 1 << 20])
def test_khatri_rao_rows_chunked_build(chunk):
    """The rank-major operand is the same whether the build walks the
    slots in chunks (with a ragged tail) or in one step, and padding
    slots (value 0) come out zero."""
    rng = np.random.default_rng(3)
    n_pad, rank, dims = 64, 20, (9, 5, 7)
    live = rng.random(n_pad) < 0.6
    vals = np.where(live, rng.standard_normal(n_pad), 0).astype(np.float32)
    idx = np.stack([np.where(live, rng.integers(0, d, n_pad), 0)
                    for d in dims]).astype(np.int32)
    factors = [rng.standard_normal((d, rank)).astype(np.float32)
               for d in dims]
    got = np.asarray(kops._khatri_rao_rows_t(
        jnp.asarray(vals), jnp.asarray(idx),
        [jnp.asarray(f) for f in factors], chunk=chunk))
    assert got.shape == (24, n_pad)                 # 20 rounded to 8s
    want = np.zeros((24, n_pad), np.float32)
    contrib = vals[:, None]
    for f, ix in zip(factors, idx):
        contrib = contrib * f[ix]
    want[:rank] = contrib.T
    np.testing.assert_array_equal(got, want)
    assert not np.any(got[:, ~live])


@pytest.mark.parametrize("ordering", ["none", "morton"])
@pytest.mark.parametrize("rows_cap,tile_rows,blk", [(64, 8, 16),
                                                    (128, 128, 32)])
def test_block_sources_gather_equals_slot_scatter(ordering, rows_cap,
                                                  tile_rows, blk):
    """Aligning stream columns by block-wide windows
    (``block_sources`` + ``_gather_blocks``) lays every element in the
    slot ``build_block_layout`` gives it and zero elsewhere, with the
    same tile per block, for sorted streams with empty tiles, a ragged
    last block and invalid elements trailing."""
    from repro.reorder import locality_keys
    rng = np.random.default_rng(11)
    cap, n_valid = 300, 260
    row = np.sort(rng.integers(0, rows_cap // 2, n_valid) * 2)
    local_row = np.zeros(cap, np.int32)
    local_row[:n_valid] = row
    valid = np.arange(cap) < n_valid
    idx_in = rng.integers(0, 1000, (cap, 2)).astype(np.int32)
    keys = locality_keys(jnp.asarray(idx_in), ordering, max_rows=1000) \
        or None
    kw = dict(rows_cap=rows_cap, blk=blk, tile_rows=tile_rows)
    slot, tob = kops.build_block_layout(
        jnp.asarray(local_row), jnp.asarray(valid), order_keys=keys, **kw)
    order, src_start, count, tob2 = kops.block_sources(
        jnp.asarray(local_row), jnp.asarray(valid), order_keys=keys, **kw)
    np.testing.assert_array_equal(np.asarray(tob2), np.asarray(tob))
    cols = np.stack([np.arange(cap) + 1, idx_in[:, 0]]).astype(np.int32)
    x = jnp.asarray(cols)
    if order is not None:
        x = jnp.take(x, order, axis=1)
    got = np.asarray(kops._gather_blocks(x, src_start, count, blk))
    n_pad = kops.n_pad_for(cap, rows_cap, blk, tile_rows)
    want = np.asarray(kops._align_to_blocks(
        jnp.asarray(np.where(valid, cols, 0).T), slot, n_pad)).T
    np.testing.assert_array_equal(got, want)
