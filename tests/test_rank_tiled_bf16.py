"""Rank-tiled + bf16-gather fused MTTKRP backends (PR-3 tentpole).

Coverage per the issue checklist:
  * exact-match vs the elementwise reference at R ∈ {128, 256, 512}
    across N ∈ {3, 4, 5} for ``pallas_fused_tiled``;
  * bf16 tolerance bounds (bf16 gathers, fp32 accumulate);
  * a hypothesis sweep asserting tiled ≡ untiled fused on small ranks;
  * dispatch tests that large-R configurations no longer fall back to
    the HBM-materialized path;
  * runtime threading: ``ModePlan.rank_slabs`` and
    ``DynasorRuntime.gather_dtype``.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import tune
from repro.core import distributed as dist
from repro.core.flycoo import build_flycoo
from repro.core.mttkrp import mttkrp_elementwise_ref, mttkrp_fused
from repro.core.tensors import random_sparse_tensor
from repro.kernels.mttkrp import kernel as kkernel
from repro.kernels.mttkrp import ops as kops

BLK, TILE = 32, 8

SHAPES = {3: (20, 16, 12), 4: (12, 10, 8, 6), 5: (8, 7, 6, 5, 4)}


def _sorted_case(shape, nnz, rank, mode, seed=0):
    rng = np.random.default_rng(seed)
    t = random_sparse_tensor(shape, nnz, seed=seed)
    order = np.argsort(t.indices[:, mode], kind="stable")
    idx = t.indices[order].astype(np.int32)
    val = t.values[order].astype(np.float32)
    factors = [jnp.asarray(rng.standard_normal((d, rank)), jnp.float32)
               for d in shape]
    return idx, val, factors


def _device_step(idx, val, valid, factors, mode, rows_cap, backend,
                 gather_dtype="float32"):
    return kops.mttkrp_device_step(
        jnp.asarray(idx), jnp.asarray(val), jnp.asarray(valid), factors,
        mode=mode, rows_cap=rows_cap, row_offset=0, blk=BLK, tile_rows=TILE,
        interpret=True, backend=backend, gather_dtype=gather_dtype)


def _rel_err(got, ref):
    return np.abs(np.asarray(got) - ref).max() / (np.abs(ref).max() + 1e-9)


# ---------------------------------------------------------------------------
# Golden: tiled kernel vs elementwise ref and vs the untiled fused kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nmodes", [3, 4, 5])
@pytest.mark.parametrize("rank", [128, 256, 512])
def test_tiled_matches_ref_and_untiled(nmodes, rank):
    shape = SHAPES[nmodes]
    idx, val, factors = _sorted_case(shape, 150, rank, 0, seed=nmodes)
    rows_cap = -(-shape[0] // TILE) * TILE
    valid = np.ones(len(val), bool)
    ref = mttkrp_elementwise_ref(idx, val, factors, 0, out_rows=rows_cap)
    tiled = _device_step(idx, val, valid, factors, 0, rows_cap,
                         "pallas_fused_tiled")
    assert _rel_err(tiled, ref) < 1e-4, (nmodes, rank)
    # Slab-wise the tiled kernel performs the identical column-independent
    # arithmetic, so it must agree with the untiled kernel bitwise.
    untiled = _device_step(idx, val, valid, factors, 0, rows_cap,
                           "pallas_fused")
    np.testing.assert_array_equal(np.asarray(tiled), np.asarray(untiled))


def test_tiled_kernel_direct_multi_slab():
    """Kernel-level: a 4-slab layout against the pure-jnp fused oracle."""
    from repro.kernels.mttkrp import ref as kref
    rng = np.random.default_rng(11)
    cap, rows_cap, rank, n_in = 200, 4 * TILE, 512, 2
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
    out = kkernel.krp_scatter(
        al(jnp.asarray(contrib)).T, al(jnp.asarray(local_row % TILE)),
        tile_of_block, rows_cap=rows_cap, blk=BLK, tile_rows=TILE,
        rank_slab=kkernel.RANK_SLAB, interpret=True)
    ref = kref.fused_mttkrp_ref(jnp.asarray(vals),
                                [jnp.asarray(r) for r in rows_list],
                                jnp.asarray(local_row), rows_cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_tiled_with_trailing_invalid_matches_materialized():
    shape = (12, 10, 8, 6)
    idx, val, factors = _sorted_case(shape, 250, 256, 0, seed=3)
    rows_cap = -(-shape[0] // TILE) * TILE
    valid = np.arange(len(val)) < len(val) - 7
    val = np.where(valid, val, 0.0).astype(np.float32)
    a = _device_step(idx, val, valid, factors, 0, rows_cap,
                     "pallas_fused_tiled")
    b = _device_step(idx, val, valid, factors, 0, rows_cap, "pallas_fused")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# bf16: tolerance bounds + traffic accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nmodes", [3, 5])
def test_bf16_tolerance_bounds(nmodes):
    """bf16 gathers round each factor row to 8 mantissa bits; the fp32
    accumulate keeps the error at the per-element rounding level: the
    Hadamard product of N−1 bf16 rows carries ≲ (N−1)·2⁻⁸ relative
    error, far below any fp32-path mismatch but clearly above exact."""
    shape = SHAPES[nmodes]
    idx, val, factors = _sorted_case(shape, 200, 128, 0, seed=nmodes)
    rows_cap = -(-shape[0] // TILE) * TILE
    valid = np.ones(len(val), bool)
    exact = np.asarray(_device_step(idx, val, valid, factors, 0, rows_cap,
                                    "pallas_fused"))
    got = np.asarray(_device_step(idx, val, valid, factors, 0, rows_cap,
                                  "pallas_fused_bf16"))
    assert got.dtype == np.float32          # accumulate stays fp32
    rel = np.abs(got - exact).max() / np.abs(exact).max()
    assert rel < 4 * (nmodes - 1) * 2.0 ** -8, rel
    assert rel > 0.0                        # it really gathered bf16


def test_bf16_composes_with_tiling():
    shape = (12, 10, 8, 6)
    idx, val, factors = _sorted_case(shape, 150, 256, 0, seed=7)
    rows_cap = -(-shape[0] // TILE) * TILE
    valid = np.ones(len(val), bool)
    a = _device_step(idx, val, valid, factors, 0, rows_cap,
                     "pallas_fused", gather_dtype="bfloat16")
    b = _device_step(idx, val, valid, factors, 0, rows_cap,
                     "pallas_fused_tiled", gather_dtype="bfloat16")
    c = _device_step(idx, val, valid, factors, 0, rows_cap,
                     "pallas_fused_bf16")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_bf16_halves_gather_budget_term():
    full = kkernel.fused_vmem_bytes(4, 1024, 512, 128)
    bf16 = kkernel.fused_vmem_bytes(4, 1024, 512, 128, gather_itemsize=2)
    gather_term = 4 * 512 * 1024 * 4
    assert full - bf16 == gather_term // 2
    # tiled working set is one slab wide, independent of padded rank
    assert kkernel.fused_tiled_vmem_bytes(4, 1024, 512, 128) == \
        kkernel.fused_tiled_vmem_bytes(4, 1 << 20, 512, 128) == \
        kkernel.fused_vmem_bytes(4, kkernel.RANK_SLAB, 512, 128)


def test_unknown_gather_dtype_rejected():
    shape = (12, 10, 8, 6)
    idx, val, factors = _sorted_case(shape, 64, 128, 0, seed=1)
    with pytest.raises(ValueError, match="gather_dtype"):
        _device_step(idx, val, np.ones(len(val), bool), factors, 0, 2 * TILE,
                     "pallas_fused", gather_dtype="float16")


# ---------------------------------------------------------------------------
# Hypothesis sweep: tiled ≡ untiled on small ranks
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    nnz=st.integers(1, 250),
    rank=st.integers(8, 200),
    nmodes=st.sampled_from([3, 4, 5]),
)
def test_tiled_equals_untiled_property(seed, nnz, rank, nmodes):
    shape = SHAPES[nmodes]
    idx, val, factors = _sorted_case(shape, nnz, rank, 0, seed=seed)
    rows_cap = -(-shape[0] // TILE) * TILE
    valid = np.ones(len(val), bool)
    tiled = _device_step(idx, val, valid, factors, 0, rows_cap,
                         "pallas_fused_tiled")
    untiled = _device_step(idx, val, valid, factors, 0, rows_cap,
                           "pallas_fused")
    assert tiled.shape == untiled.shape == (rows_cap, rank)
    np.testing.assert_array_equal(np.asarray(tiled), np.asarray(untiled))


# ---------------------------------------------------------------------------
# Dispatch: the large-R cliff onto the materialized path is gone
# ---------------------------------------------------------------------------

def test_large_rank_no_longer_falls_back_to_materialized():
    # Configurations the PR-2 static rule sent to `pallas` purely on
    # VMEM grounds (full-rank fused working set > budget): the slabbed
    # working set fits, so the dispatch keeps a fused variant.
    for nmodes, rank, blk in [(5, 16384, 512), (5, 4096, 2048),
                              (4, 8192, 2048)]:
        assert not kops.fused_fits_vmem(nmodes, rank, blk, 128)
        got = kops.select_backend("auto", nmodes=nmodes, rank=rank,
                                  blk=blk, tile_rows=128)
        assert got == "pallas_fused_tiled", (nmodes, rank, blk)


def test_auto_prefers_untiled_fused_when_it_fits():
    # No regression at moderate rank: untiled fused still wins (no slab
    # re-streaming of the scalar streams).
    assert kops.select_backend("auto", nmodes=4, rank=256) == "pallas_fused"


def test_min_mxu_rank_threads_the_mxu_multiple():
    # One constant: MXU lane width 128, guard = 128/16 = 8, slab = 128.
    assert kops.MXU_RANK_MULTIPLE == kkernel.MXU_RANK_MULTIPLE \
        == kkernel.RANK_SLAB
    assert kops.MIN_MXU_RANK == kops.MXU_RANK_MULTIPLE // 16
    assert kops.padded_rank(1) == kops.MXU_RANK_MULTIPLE
    assert kops.select_backend(
        "auto", nmodes=3, rank=kops.MIN_MXU_RANK - 1) == "ref"


# ---------------------------------------------------------------------------
# Runtime threading: ModePlan.rank_slabs + DynasorRuntime.gather_dtype
# ---------------------------------------------------------------------------

def _tiled_loving_table(rank_knots=(128, 512)):
    entries = [
        tune.CalibrationEntry(
            nmodes=3, rank=r, blk=32, tile_rows=8, density=1.0,
            timings_s={"pallas_fused_tiled": 0.001, "pallas_fused": 1.0,
                       "ref": 1.0})
        for r in rank_knots
    ]
    return tune.CalibrationTable(entries=entries)


def test_plan_modes_records_rank_slabs():
    t = random_sparse_tensor((40, 30, 20), 400, seed=3,
                             distribution="powerlaw")
    ft = build_flycoo(t, 4, m_bounds=(4, 16), g_bounds=(8, 64),
                      cache_bytes=1 << 20)
    plans = tune.plan_modes(_tiled_loving_table(), ft, 512)
    assert plans is not None
    for p in plans:
        assert p.backend == "pallas_fused_tiled"
        assert p.rank_slabs == kops.padded_rank(512) // kops.MXU_RANK_MULTIPLE
    # non-tiled plans carry the trivial single slab
    plans16 = tune.plan_modes(tune.calibrate(
        measure=lambda b, p: {"segsum": 0.1}.get(b, 1.0), quick=True), ft, 16)
    assert plans16 is not None and all(p.rank_slabs == 1 for p in plans16)


def test_runtime_threads_gather_dtype():
    t = random_sparse_tensor((40, 30, 20), 400, seed=3,
                             distribution="powerlaw")
    ft = build_flycoo(t, 4, m_bounds=(4, 16), g_bounds=(8, 64),
                      cache_bytes=1 << 20)
    rt, _ = dist.prepare_runtime(ft, rank=16, tile_rows=8)
    assert rt.gather_dtype == "float32"      # default unchanged
    rt_bf, _ = dist.prepare_runtime(ft, rank=16, tile_rows=8,
                                    gather_dtype="bfloat16")
    assert rt_bf.gather_dtype == "bfloat16"
    # back-compat direct construction without the new fields
    rt_old = dist.DynasorRuntime(
        num_workers=1, nmodes=3, rank=8, rows_cap=(8, 8, 8),
        i_pad=(8, 8, 8), nnz_cap=8, bucket_cap=8, shape=(8, 8, 8))
    assert rt_old.gather_dtype == "float32"
    assert rt_old.plan_for(0, "pallas_fused_tiled").backend == \
        "pallas_fused_tiled"
    # typos fail at construction, not silently mid-decomposition
    with pytest.raises(ValueError, match="gather_dtype"):
        dist.prepare_runtime(ft, rank=16, tile_rows=8, gather_dtype="bf16")


def test_plan_for_rederives_rank_slabs():
    """rank_slabs always reflects the *resolved* backend."""
    tuned = dist.DynasorRuntime(
        num_workers=1, nmodes=3, rank=512, rows_cap=(8, 8, 8),
        i_pad=(8, 8, 8), nnz_cap=8, bucket_cap=8, shape=(8, 8, 8),
        mode_plans=(dist.ModePlan("pallas_fused_tiled", 32, 8, 4),) * 3)
    # explicit non-tiled override must not carry the tuned plan's slabs
    assert tuned.plan_for(0, "pallas_fused").rank_slabs == 1
    assert tuned.plan_for(0, "auto").rank_slabs == 4
    # explicit tiled backend on an untuned runtime gets the real count
    untuned = dist.DynasorRuntime(
        num_workers=1, nmodes=3, rank=512, rows_cap=(8, 8, 8),
        i_pad=(8, 8, 8), nnz_cap=8, bucket_cap=8, shape=(8, 8, 8))
    assert untuned.plan_for(0, "pallas_fused_tiled").rank_slabs == \
        kops.padded_rank(512) // kops.MXU_RANK_MULTIPLE == 4
    assert untuned.plan_for(0, "pallas_fused").rank_slabs == 1


def test_mttkrp_fused_wrapper_gather_dtype():
    shape, rank = (14, 11, 9), 128
    t = random_sparse_tensor(shape, 150, seed=9)
    rng = np.random.default_rng(9)
    factors = [jnp.asarray(rng.standard_normal((d, rank)), jnp.float32)
               for d in shape]
    exact = mttkrp_fused(jnp.asarray(t.indices), jnp.asarray(t.values),
                         factors, 0, shape[0], blk=BLK, tile_rows=TILE,
                         backend="pallas_fused_tiled")
    approx = mttkrp_fused(jnp.asarray(t.indices), jnp.asarray(t.values),
                          factors, 0, shape[0], blk=BLK, tile_rows=TILE,
                          backend="pallas_fused_tiled",
                          gather_dtype="bfloat16")
    ref = mttkrp_elementwise_ref(t.indices, t.values, factors, 0)
    assert _rel_err(exact, ref) < 1e-4
    assert 0.0 < _rel_err(approx, np.asarray(exact)) < 0.05
