"""N-mode fused MTTKRP kernel + backend dispatch (golden tests).

Tentpole coverage: the ``pallas_fused`` rung vs. the literal elementwise
reference on 2-/3-/4-/5-mode tensors across *all* output modes, the edge
cases of the blocked layout (empty shards, all-padding blocks, unaligned
rank, single output tile), and the ``auto`` dispatch decisions.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.mttkrp import mttkrp_elementwise_ref, mttkrp_fused
from repro.core.tensors import random_sparse_tensor
from repro.kernels.mttkrp import kernel as kkernel
from repro.kernels.mttkrp import ops as kops
from repro.oocore import planner
from repro.kernels.mttkrp import ref as kref

BLK, TILE = 32, 8


def _sorted_case(shape, nnz, rank, mode, seed=0):
    """Random COO stream sorted by the output mode + random factors."""
    rng = np.random.default_rng(seed)
    t = random_sparse_tensor(shape, nnz, seed=seed)
    order = np.argsort(t.indices[:, mode], kind="stable")
    idx = t.indices[order].astype(np.int32)
    val = t.values[order].astype(np.float32)
    factors = [jnp.asarray(rng.standard_normal((d, rank)), jnp.float32)
               for d in shape]
    return idx, val, factors


def _device_step(idx, val, valid, factors, mode, rows_cap, backend):
    return kops.mttkrp_device_step(
        jnp.asarray(idx), jnp.asarray(val), jnp.asarray(valid), factors,
        mode=mode, rows_cap=rows_cap, row_offset=0, blk=BLK, tile_rows=TILE,
        interpret=True, backend=backend)


def _rel_err(got, ref):
    return np.abs(np.asarray(got) - ref).max() / (np.abs(ref).max() + 1e-9)


# ---------------------------------------------------------------------------
# Golden: fused N-mode == elementwise reference, all modes, orders 2..5
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [
    (30, 4),                 # 2-mode: single input-factor operand
    (20, 16, 12),            # 3-mode (the old special case)
    (12, 10, 8, 6),          # 4-mode
    (8, 7, 6, 5, 4),         # 5-mode
])
def test_fused_nmode_matches_elementwise_ref_all_modes(shape):
    nnz, rank = 180, 16
    for mode in range(len(shape)):
        idx, val, factors = _sorted_case(shape, nnz, rank, mode, seed=mode)
        rows_cap = -(-shape[mode] // TILE) * TILE
        valid = np.ones(len(val), bool)
        ref = mttkrp_elementwise_ref(idx, val, factors, mode,
                                     out_rows=rows_cap)
        got = _device_step(idx, val, valid, factors, mode, rows_cap,
                           "pallas_fused")
        assert _rel_err(got, ref) < 1e-4, (shape, mode)


@pytest.mark.parametrize("shape", [(20, 16, 12), (12, 10, 8, 6)])
def test_fused_agrees_with_materialized_pallas(shape):
    idx, val, factors = _sorted_case(shape, 250, 24, 0, seed=3)
    rows_cap = -(-shape[0] // TILE) * TILE
    valid = np.arange(len(val)) < len(val) - 7    # trailing invalid
    val = np.where(valid, val, 0.0).astype(np.float32)
    # Against the XLA segment-sum rung, which forms the same rows and
    # sums them in another order.
    a = _device_step(idx, val, valid, factors, 0, rows_cap, "pallas_fused")
    b = _device_step(idx, val, valid, factors, 0, rows_cap, "ref")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_device_step_defaults_to_pallas_fused():
    shape = (12, 10, 8, 6)
    idx, val, factors = _sorted_case(shape, 250, 24, 0, seed=5)
    rows_cap = -(-shape[0] // TILE) * TILE
    valid = np.ones(len(val), bool)
    default = kops.mttkrp_device_step(
        jnp.asarray(idx), jnp.asarray(val), jnp.asarray(valid), factors,
        mode=0, rows_cap=rows_cap, row_offset=0, blk=BLK, tile_rows=TILE,
        interpret=True)
    explicit = _device_step(idx, val, valid, factors, 0, rows_cap,
                            "pallas_fused")
    np.testing.assert_array_equal(np.asarray(default), np.asarray(explicit))


def _krp_case(seed, cap, rows_cap, rank, n_in):
    """A hand-built aligned layout and its rank-major Khatri-Rao operand."""
    rng = np.random.default_rng(seed)
    local_row = np.sort(rng.integers(0, rows_cap, cap)).astype(np.int32)
    vals = rng.standard_normal(cap).astype(np.float32)
    rows_list = [rng.standard_normal((cap, rank)).astype(np.float32)
                 for _ in range(n_in)]
    n_pad = kops.n_pad_for(cap, rows_cap, BLK, TILE)
    slot, tile_of_block = kops.build_block_layout(
        jnp.asarray(local_row), jnp.ones(cap, bool), rows_cap=rows_cap,
        blk=BLK, tile_rows=TILE)
    al = lambda x: jnp.zeros((n_pad + 1,) + x.shape[1:], x.dtype)\
        .at[slot].set(x)[:-1]
    contrib = vals[:, None] * np.prod(rows_list, axis=0)
    krp_t = jnp.pad(al(jnp.asarray(contrib)).T,
                    ((0, kkernel.krp_rows(rank) - rank), (0, 0)))
    args = (krp_t, al(jnp.asarray(local_row % TILE)), tile_of_block)
    ref = kref.fused_mttkrp_ref(jnp.asarray(vals),
                                [jnp.asarray(r) for r in rows_list],
                                jnp.asarray(local_row), rows_cap)
    return args, ref


def test_krp_scatter_direct_vs_fused_ref():
    """Kernel-level: hand-built aligned layout, 4-mode, vs the jnp oracle."""
    rows_cap, rank = 4 * TILE, 24
    args, ref = _krp_case(5, 200, rows_cap, rank, 3)
    assert args[0].shape[0] == 24            # 24 rows: no lane padding
    out = kkernel.krp_scatter(*args, rows_cap=rows_cap, blk=BLK,
                              tile_rows=TILE, interpret=True)
    assert out.shape == (rows_cap, 128)
    np.testing.assert_allclose(np.asarray(out)[:, :rank], np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    assert not np.any(np.asarray(out)[:, rank:])


def test_krp_scatter_slabbed_equals_whole():
    """A pass per 128-column slab does each column's arithmetic as one
    pass does: bitwise equal."""
    rows_cap = 2 * TILE
    args, _ = _krp_case(7, 100, rows_cap, 256, 2)
    kw = dict(rows_cap=rows_cap, blk=BLK, tile_rows=TILE, interpret=True)
    whole = kkernel.krp_scatter(*args, **kw)
    slabbed = kkernel.krp_scatter(*args, rank_slab=kkernel.RANK_SLAB, **kw)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(slabbed))


def test_mttkrp_fused_wrapper_matches_ref():
    """core.mttkrp.mttkrp_fused (sort + dispatch) == elementwise ref."""
    shape, rank = (14, 11, 9, 7), 16
    t = random_sparse_tensor(shape, 150, seed=9)
    rng = np.random.default_rng(9)
    factors = [jnp.asarray(rng.standard_normal((d, rank)), jnp.float32)
               for d in shape]
    for mode in range(len(shape)):
        ref = mttkrp_elementwise_ref(t.indices, t.values, factors, mode)
        got = mttkrp_fused(jnp.asarray(t.indices), jnp.asarray(t.values),
                           factors, mode, shape[mode], blk=BLK,
                           tile_rows=TILE)
        assert _rel_err(got, ref) < 1e-4, mode


# ---------------------------------------------------------------------------
# Edge cases
# ---------------------------------------------------------------------------

def test_empty_shard_all_invalid_gives_zeros():
    shape = (12, 10, 8, 6)
    idx, val, factors = _sorted_case(shape, 64, 16, 0, seed=1)
    rows_cap = -(-shape[0] // TILE) * TILE
    valid = np.zeros(len(val), bool)
    val = np.zeros_like(val)
    out = _device_step(idx, val, valid, factors, 0, rows_cap, "pallas_fused")
    np.testing.assert_array_equal(np.asarray(out), 0.0)


def test_all_padding_blocks_between_sparse_tiles():
    """Nonzeros touch only the first and last tile — middle tiles stay 0."""
    shape = (8 * TILE, 10, 6, 5)
    rng = np.random.default_rng(2)
    cap, rank = 96, 16
    rows = np.concatenate([rng.integers(0, TILE, cap // 2),
                           rng.integers(7 * TILE, 8 * TILE, cap // 2)])
    rows.sort()
    idx = np.stack([rows] + [rng.integers(0, d, cap) for d in shape[1:]],
                   axis=1).astype(np.int32)
    val = rng.standard_normal(cap).astype(np.float32)
    factors = [jnp.asarray(rng.standard_normal((d, rank)), jnp.float32)
               for d in shape]
    valid = np.ones(cap, bool)
    ref = mttkrp_elementwise_ref(idx, val, factors, 0, out_rows=shape[0])
    got = _device_step(idx, val, valid, factors, 0, shape[0], "pallas_fused")
    assert _rel_err(got, ref) < 1e-4
    np.testing.assert_array_equal(np.asarray(got)[TILE:7 * TILE], 0.0)


@pytest.mark.parametrize("rank", [9, 24, 130])
def test_rank_not_multiple_of_128(rank):
    """Fused path pads rank to the MXU lane width and slices back."""
    shape = (16, 10, 8, 6)
    idx, val, factors = _sorted_case(shape, 120, rank, 0, seed=4)
    rows_cap = TILE * 2
    valid = np.ones(len(val), bool)
    ref = mttkrp_elementwise_ref(idx, val, factors, 0, out_rows=rows_cap)
    got = _device_step(idx, val, valid, factors, 0, rows_cap, "pallas_fused")
    assert got.shape == (rows_cap, rank)
    assert _rel_err(got, ref) < 1e-4


def test_single_output_tile():
    shape = (TILE, 9, 7, 5, 3)          # rows_cap == tile_rows, 5-mode
    idx, val, factors = _sorted_case(shape, 100, 16, 0, seed=6)
    valid = np.ones(len(val), bool)
    ref = mttkrp_elementwise_ref(idx, val, factors, 0, out_rows=TILE)
    got = _device_step(idx, val, valid, factors, 0, TILE, "pallas_fused")
    assert _rel_err(got, ref) < 1e-4


# ---------------------------------------------------------------------------
# Dispatch layer
# ---------------------------------------------------------------------------

def test_auto_picks_fused_when_eligible():
    assert kops.select_backend("auto", nmodes=3, rank=64) == "pallas_fused"
    assert kops.select_backend("auto", nmodes=4, rank=128) == "pallas_fused"
    assert kops.select_backend("auto", nmodes=5, rank=32) == "pallas_fused"


def test_auto_falls_back_on_tiny_rank():
    # rank < 8: one-hot MXU matmul would be ≥ 16x padding — segment-sum ref.
    assert kops.select_backend("auto", nmodes=3, rank=4) == "ref"
    assert kops.select_backend("auto", nmodes=5, rank=7) == "ref"


def test_auto_degrades_to_tiled_then_materialized_on_vmem_pressure():
    # Budget below the full-rank working set of the one Khatri-Rao
    # operand, but above one rank slab: the rank-tiled rung.
    k = planner.KRP_OPERANDS
    tight = kkernel.fused_vmem_bytes(k, 256, 512, 128) - 1
    assert kkernel.fused_tiled_vmem_bytes(k, 256, 512, 128) < tight
    assert kops.select_backend("auto", nmodes=4, rank=256,
                               vmem_budget=tight) == "pallas_fused_tiled"
    # The kernel takes one operand whatever N is, so fewer input modes
    # change nothing.
    assert kops.select_backend(
        "auto", nmodes=2, rank=256, vmem_budget=tight) == "pallas_fused_tiled"
    assert kops.select_backend(
        "auto", nmodes=2, rank=256, vmem_budget=tight + 1) == "pallas_fused"
    # Budget below even one slab -> no Pallas rung fits, and the XLA
    # segment sum, which holds nothing in VMEM, is the last resort.
    tiny = kkernel.fused_tiled_vmem_bytes(k, 256, 512, 128) - 1
    assert kops.select_backend("auto", nmodes=4, rank=256,
                               vmem_budget=tiny) == "ref"


def test_explicit_backends_pass_through():
    for b in kops.BACKENDS:
        assert kops.select_backend(b, nmodes=4, rank=4) == b


def test_unknown_backend_rejected():
    # A typo'd backend must not silently fall through to the materialized
    # path ("segsum" lives in core.distributed, not here).
    for b in ("palas_fused", "segsum", ""):
        with pytest.raises(ValueError, match="unknown MTTKRP backend"):
            kops.select_backend(b, nmodes=4, rank=16)


def test_unknown_backend_rejected_at_distributed_layer():
    # ...and must not silently fall through to segsum one layer up either.
    from repro.core import distributed as dist
    rt = dist.DynasorRuntime(
        num_workers=1, nmodes=3, rank=8, rows_cap=(8, 8, 8),
        i_pad=(8, 8, 8), nnz_cap=8, bucket_cap=8, shape=(8, 8, 8))
    with pytest.raises(ValueError, match="unknown MTTKRP backend"):
        dist.device_mttkrp(jnp.zeros((8, 3), jnp.int32), jnp.zeros(8),
                           jnp.ones(8, bool), [jnp.ones((8, 8))] * 3,
                           0, rt, "pallas_fussed")


def test_auto_end_to_end_matches_ref():
    """backend='auto' through mttkrp_device_step on an eligible 4-mode case."""
    shape = (12, 10, 8, 6)
    idx, val, factors = _sorted_case(shape, 150, 16, 2, seed=8)
    rows_cap = -(-shape[2] // TILE) * TILE
    valid = np.ones(len(val), bool)
    ref = mttkrp_elementwise_ref(idx, val, factors, 2, out_rows=rows_cap)
    got = _device_step(idx, val, valid, factors, 2, rows_cap, "auto")
    assert _rel_err(got, ref) < 1e-4
