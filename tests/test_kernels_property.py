"""Property-based Pallas kernel sweep: random shapes/blocks vs the oracle
(per assignment: hypothesis sweeps for each Pallas kernel), plus the
``build_block_layout`` invariants every kernel's correctness rides on."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.mttkrp import ops as kops
from repro.kernels.mttkrp import ref as kref


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    n_el=st.integers(1, 400),
    tiles=st.integers(1, 6),
    tile_rows=st.sampled_from([8, 16, 32]),
    blk=st.sampled_from([16, 32, 64]),
    rank=st.integers(1, 24),
    frac_invalid=st.floats(0.0, 0.4),
)
def test_segment_accumulate_property(seed, n_el, tiles, tile_rows, blk,
                                     rank, frac_invalid):
    rows_cap = tiles * tile_rows
    rng = np.random.default_rng(seed)
    row = np.sort(rng.integers(0, rows_cap, n_el)).astype(np.int32)
    contrib = rng.standard_normal((n_el, rank)).astype(np.float32)
    valid = np.ones(n_el, bool)
    k = int(n_el * frac_invalid)
    if k:
        valid[-k:] = False
        contrib[-k:] = 0.0
        row[-k:] = rows_cap - 1
    out = kops.mttkrp_blocked(jnp.asarray(contrib), jnp.asarray(row),
                              jnp.asarray(valid), rows_cap=rows_cap,
                              blk=blk, tile_rows=tile_rows, interpret=True)
    ref = kref.segment_accumulate_ref(
        jnp.asarray(np.where(valid[:, None], contrib, 0)),
        jnp.asarray(np.where(valid, row, 0)), rows_cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=12, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    cap=st.integers(8, 200),
    rows_cap=st.sampled_from([16, 32, 64]),
    rank=st.integers(1, 16),
)
def test_fused_3mode_property(seed, cap, rows_cap, rank):
    rng = np.random.default_rng(seed)
    idx = np.stack([
        np.sort(rng.integers(0, rows_cap, cap)),
        rng.integers(0, 40, cap),
        rng.integers(0, 24, cap),
    ], axis=1).astype(np.int32)
    val = rng.standard_normal(cap).astype(np.float32)
    valid = rng.random(cap) > 0.2
    # invalid entries must trail (FLYCOO pack invariant)
    order = np.argsort(~valid, kind="stable")
    idx, val, valid = idx[order], val[order], valid[order]
    idx[:, 0] = np.sort(idx[:, 0])
    factors = [jnp.asarray(rng.standard_normal((n, rank)), jnp.float32)
               for n in (rows_cap, 40, 24)]
    kw = dict(mode=0, rows_cap=rows_cap, row_offset=0, blk=16, tile_rows=8,
              interpret=True)
    ref = kops.mttkrp_device_step(jnp.asarray(idx), jnp.asarray(val),
                                  jnp.asarray(valid), factors,
                                  backend="ref", **kw)
    got = kops.mttkrp_device_step(jnp.asarray(idx), jnp.asarray(val),
                                  jnp.asarray(valid), factors,
                                  backend="pallas_fused", **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    n_el=st.integers(1, 300),
    tiles=st.integers(1, 6),
    tile_rows=st.sampled_from([8, 16, 32]),
    blk=st.sampled_from([16, 32, 64]),
    frac_invalid=st.floats(0.0, 0.5),
)
def test_build_block_layout_invariants(seed, n_el, tiles, tile_rows, blk,
                                       frac_invalid):
    """The layout contract every Pallas kernel here relies on:

      * valid elements get *injective* in-range slots; invalid elements
        all land on the dump slot ``n_pad``;
      * blocks are homogeneous per tile: a block never straddles an
        output row tile (each tile's run starts on a block boundary);
      * ``tile_of_block`` is non-decreasing and consistent with the
        slots — every valid element's block is attributed to exactly
        its own output tile.
    """
    rows_cap = tiles * tile_rows
    rng = np.random.default_rng(seed)
    row = np.sort(rng.integers(0, rows_cap, n_el)).astype(np.int32)
    valid = np.ones(n_el, bool)
    k = int(n_el * frac_invalid)
    if k:
        valid[-k:] = False          # invalid trail (FLYCOO pack invariant)
    n_pad = kops.n_pad_for(n_el, rows_cap, blk, tile_rows)
    slot, tile_of_block = kops.build_block_layout(
        jnp.asarray(row), jnp.asarray(valid), rows_cap=rows_cap,
        blk=blk, tile_rows=tile_rows)
    slot = np.asarray(slot)
    tile_of_block = np.asarray(tile_of_block)

    assert tile_of_block.shape == (n_pad // blk,)
    # invalid elements -> the dump slot, valid -> in-range
    assert np.all(slot[~valid] == n_pad)
    vslots = slot[valid]
    assert np.all((0 <= vslots) & (vslots < n_pad))
    # injectivity
    assert len(np.unique(vslots)) == len(vslots)

    vtile = row[valid] // tile_rows
    # consistency: each element's block is attributed to its own tile
    assert np.array_equal(tile_of_block[vslots // blk], vtile)
    # block-aligned per tile: every tile's first slot is a block boundary
    # and its elements occupy consecutive slots (sorted-run compaction)
    for t in np.unique(vtile):
        s = np.sort(vslots[vtile == t])
        assert s[0] % blk == 0, (t, s[0])
        assert np.array_equal(s, s[0] + np.arange(len(s)))
    # non-decreasing tile per block
    assert np.all(np.diff(tile_of_block) >= 0)


def _layout_oracle(row, valid, rows_cap, blk, tile_rows, order_keys=None):
    """numpy ``build_block_layout``: run starts by ``np.searchsorted`` over
    the (lex)sorted tile stream, as ``oocore.planner`` computes them."""
    cap = row.shape[0]
    num_tiles = rows_cap // tile_rows
    n_pad = kops.n_pad_for(cap, rows_cap, blk, tile_rows)
    tile = np.where(valid, row // tile_rows, num_tiles).astype(np.int64)
    counts = np.bincount(tile[valid], minlength=num_tiles)[:num_tiles]
    padded = ((counts + blk - 1) // blk) * blk
    offsets = np.concatenate([[0], np.cumsum(padded)])
    pos = np.arange(cap)
    if order_keys is None:
        position, sorted_tiles = pos, tile
    else:
        order = np.lexsort((pos,) + tuple(order_keys)[::-1] + (tile,))
        position = np.empty(cap, np.int64)
        position[order] = pos
        sorted_tiles = tile[order]
    first = np.searchsorted(sorted_tiles, tile, side="left")
    slot = np.where(valid, offsets[tile] + position - first, n_pad)
    block_start = np.arange(n_pad // blk) * blk
    tile_of_block = np.clip(
        np.searchsorted(offsets, block_start, side="right") - 1,
        0, num_tiles - 1)
    return slot, tile_of_block


def _layout_input(rng, n_el, rows_cap, n_invalid, keyed, tiles_used=None):
    """A stream as the callers hand it: valid rows ascending (shuffled
    when ranked by keys), invalid elements trailing."""
    if tiles_used is None:
        rows = rng.integers(0, rows_cap, n_el)
    else:
        rows = rng.choice(tiles_used, n_el)
    row = np.sort(rows).astype(np.int32)
    valid = np.ones(n_el, bool)
    if n_invalid:
        valid[-n_invalid:] = False
    keys = None
    if keyed:
        nv = n_el - n_invalid
        row[:nv] = rng.permutation(row[:nv])
        keys = (rng.integers(0, 4, n_el).astype(np.int32),
                rng.integers(0, 64, n_el).astype(np.int32))
    return row, valid, keys


def _assert_layout_matches_oracle(row, valid, keys, rows_cap, blk,
                                  tile_rows):
    slot, tile_of_block = kops.build_block_layout(
        jnp.asarray(row), jnp.asarray(valid), rows_cap=rows_cap, blk=blk,
        tile_rows=tile_rows,
        order_keys=None if keys is None else tuple(map(jnp.asarray, keys)))
    want_slot, want_tob = _layout_oracle(row, valid, rows_cap, blk,
                                         tile_rows, keys)
    assert slot.dtype == jnp.int32 and tile_of_block.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(slot), want_slot)
    np.testing.assert_array_equal(np.asarray(tile_of_block), want_tob)


@settings(max_examples=25, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    n_el=st.integers(1, 300),
    tiles=st.integers(1, 6),
    tile_rows=st.sampled_from([8, 16, 32]),
    blk=st.sampled_from([16, 32, 64]),
    frac_invalid=st.floats(0.0, 1.0),
    keyed=st.booleans(),
)
def test_build_block_layout_matches_searchsorted_oracle(
        seed, n_el, tiles, tile_rows, blk, frac_invalid, keyed):
    """Run starts from the per-tile counts give the slots and block tiles
    a stream-sized binary search gives, bit for bit, on both branches."""
    rows_cap = tiles * tile_rows
    rng = np.random.default_rng(seed)
    row, valid, keys = _layout_input(rng, n_el, rows_cap,
                                     int(n_el * frac_invalid), keyed)
    _assert_layout_matches_oracle(row, valid, keys, rows_cap, blk,
                                  tile_rows)


@pytest.mark.parametrize("keyed", [False, True], ids=["sorted", "keyed"])
@pytest.mark.parametrize("case", ["empty_tiles", "one_tile", "all_invalid",
                                  "invalid_trailing"])
def test_build_block_layout_matches_oracle_edge_cases(case, keyed):
    tile_rows, blk = 8, 16
    tiles = 1 if case == "one_tile" else 6
    rows_cap = tiles * tile_rows
    n_el = 97
    n_invalid = {"all_invalid": n_el, "invalid_trailing": 40}.get(case, 0)
    # empty_tiles: rows only in tiles 1 and 4, so tiles 0, 2, 3, 5 are empty
    used = np.r_[8:16, 32:40] if case == "empty_tiles" else None
    row, valid, keys = _layout_input(np.random.default_rng(7), n_el,
                                     rows_cap, n_invalid, keyed, used)
    _assert_layout_matches_oracle(row, valid, keys, rows_cap, blk,
                                  tile_rows)


@pytest.mark.parametrize("keyed", [False, True], ids=["sorted", "keyed"])
def test_build_block_layout_lowers_one_search_over_blocks(keyed):
    """The lowered layout build holds one ``while``: the block→tile search
    over ``n_pad // blk`` block starts. A search over the stream would add
    a loop carrying ``(cap,)`` arrays."""
    import jax

    cap, tiles, tile_rows, blk = 1000, 8, 8, 32
    rows_cap = tiles * tile_rows
    n_blocks = kops.n_pad_for(cap, rows_cap, blk, tile_rows) // blk
    assert n_blocks != cap
    args = [jax.ShapeDtypeStruct((cap,), jnp.int32),
            jax.ShapeDtypeStruct((cap,), jnp.bool_)]
    if keyed:
        args.append(jax.ShapeDtypeStruct((cap,), jnp.int32))

    def layout(row, valid, *keys):
        return kops.build_block_layout(row, valid, rows_cap=rows_cap,
                                       blk=blk, tile_rows=tile_rows,
                                       order_keys=keys or None)

    text = jax.jit(layout).lower(*args).as_text()
    loops = [ln for ln in text.splitlines() if "stablehlo.while(" in ln]
    assert len(loops) == 1, loops
    assert f"tensor<{n_blocks}xi32>" in loops[0]
    assert f"tensor<{cap}x" not in loops[0]
