"""In-kernel factor gather backends (PR-4 tentpole).

Coverage per the issue checklist:
  * bit-exactness of ``pallas_fused_gather`` (and its rank-tiled and
    bf16 compositions) vs the HBM-materializing ``pallas_fused`` path
    at R ∈ {128, 256, 512} across N ∈ {3, 4, 5};
  * trailing-invalid handling and the elementwise reference;
  * VMEM accounting: the index-stream term, bf16 residency halving,
    slab independence of the tiled resident set;
  * no-fallback dispatch: ``select_backend`` prefers the gather family
    whenever its VMEM predicate holds (``factor_rows`` supplied), is
    bit-identical to the old decisions when it isn't, and a calibration
    table cannot steer onto an uncertifiable gather choice;
  * runtime threading: ``ModePlan.rank_slabs`` for the tiled gather
    backend and tuned ``plan_modes`` feasibility;
  * schema back-compat: the committed v2 calibration table still loads.
"""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro import tune
from repro.core import distributed as dist
from repro.core.mttkrp import mttkrp_elementwise_ref
from repro.core.tensors import random_sparse_tensor
from repro.kernels.mttkrp import kernel as kkernel
from repro.kernels.mttkrp import ops as kops

BLK, TILE = 32, 8

SHAPES = {3: (20, 16, 12), 4: (12, 10, 8, 6), 5: (8, 7, 6, 5, 4)}

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
# Golden: in-kernel gather vs the materializing fused kernel, bit-exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nmodes", [3, 4, 5])
@pytest.mark.parametrize("rank", [128, 256, 512])
def test_gather_bitexact_vs_fused(nmodes, rank):
    """The gather kernel performs the identical fp32 arithmetic in the
    identical order — only *where* the rows are fetched changes — so it
    must agree with the fused kernel bitwise, not just within
    tolerance."""
    shape = SHAPES[nmodes]
    idx, val, factors = _sorted_case(shape, 150, rank, 0, seed=nmodes)
    rows_cap = -(-shape[0] // TILE) * TILE
    valid = np.ones(len(val), bool)
    fused = _device_step(idx, val, valid, factors, 0, rows_cap,
                         "pallas_fused")
    gather = _device_step(idx, val, valid, factors, 0, rows_cap,
                          "pallas_fused_gather")
    tiled = _device_step(idx, val, valid, factors, 0, rows_cap,
                         "pallas_fused_gather_tiled")
    np.testing.assert_array_equal(np.asarray(gather), np.asarray(fused))
    np.testing.assert_array_equal(np.asarray(tiled), np.asarray(fused))
    ref = mttkrp_elementwise_ref(idx, val, factors, 0, out_rows=rows_cap)
    assert _rel_err(gather, ref) < 1e-4, (nmodes, rank)


def test_gather_nonzero_output_mode():
    shape = (12, 10, 8, 6)
    idx, val, factors = _sorted_case(shape, 200, 128, 2, seed=5)
    rows_cap = -(-shape[2] // TILE) * TILE
    valid = np.ones(len(val), bool)
    fused = _device_step(idx, val, valid, factors, 2, rows_cap,
                         "pallas_fused")
    gather = _device_step(idx, val, valid, factors, 2, rows_cap,
                          "pallas_fused_gather")
    np.testing.assert_array_equal(np.asarray(gather), np.asarray(fused))


def test_gather_with_trailing_invalid():
    shape = (12, 10, 8, 6)
    idx, val, factors = _sorted_case(shape, 250, 256, 0, seed=3)
    rows_cap = -(-shape[0] // TILE) * TILE
    valid = np.arange(len(val)) < len(val) - 7
    val = np.where(valid, val, 0.0).astype(np.float32)
    a = _device_step(idx, val, valid, factors, 0, rows_cap,
                     "pallas_fused_gather")
    b = _device_step(idx, val, valid, factors, 0, rows_cap, "pallas_fused")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)


def test_gather_bf16_compositions_match_materialized_bf16():
    """Casting the resident matrices to bf16 must equal the materialized
    path's cast-then-take bitwise, across all four bf16 spellings."""
    shape = (12, 10, 8, 6)
    idx, val, factors = _sorted_case(shape, 150, 256, 0, seed=7)
    rows_cap = -(-shape[0] // TILE) * TILE
    valid = np.ones(len(val), bool)
    want = _device_step(idx, val, valid, factors, 0, rows_cap,
                        "pallas_fused_bf16")
    got_name = _device_step(idx, val, valid, factors, 0, rows_cap,
                            "pallas_fused_gather_bf16")
    got_dtype = _device_step(idx, val, valid, factors, 0, rows_cap,
                             "pallas_fused_gather",
                             gather_dtype="bfloat16")
    got_tiled = _device_step(idx, val, valid, factors, 0, rows_cap,
                             "pallas_fused_gather_tiled",
                             gather_dtype="bfloat16")
    assert np.asarray(got_name).dtype == np.float32   # fp32 accumulate
    np.testing.assert_array_equal(np.asarray(got_name), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_dtype), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_tiled), np.asarray(want))
    exact = _device_step(idx, val, valid, factors, 0, rows_cap,
                         "pallas_fused_gather")
    rel = _rel_err(got_name, np.asarray(exact))
    assert 0.0 < rel < 4 * 3 * 2.0 ** -8              # it really gathered bf16


# ---------------------------------------------------------------------------
# VMEM accounting: index-stream term + resident-factor formulas
# ---------------------------------------------------------------------------

def test_fused_vmem_bytes_index_stream_term():
    # vals (fp32) + rows (int32) = 2·blk·4; the gather family adds one
    # int32 index stream per input mode.
    base = kkernel.fused_vmem_bytes(2, 256, 512, 128)
    with_idx = kkernel.fused_vmem_bytes(2, 256, 512, 128,
                                        index_stream_modes=2)
    assert with_idx - base == 2 * 512 * 4


def test_gather_vmem_bytes_formulas():
    k, rpad, blk, tile, fr = 3, 512, 512, 128, 10_000
    got = kkernel.gather_vmem_bytes(k, rpad, blk, tile, fr)
    resident = fr * rpad * 4
    contrib = blk * rpad * 4
    onehot = blk * tile * 4
    out_tile = tile * rpad * 4
    scalars = (2 + k) * blk * 4
    assert got == resident + contrib + onehot + out_tile + scalars
    # bf16 halves exactly the resident-factor term
    bf16 = kkernel.gather_vmem_bytes(k, rpad, blk, tile, fr,
                                     gather_itemsize=2)
    assert got - bf16 == resident // 2
    # the tiled resident set is one slab wide: independent of padded rank
    assert kkernel.gather_tiled_vmem_bytes(k, rpad, blk, tile, fr) == \
        kkernel.gather_tiled_vmem_bytes(k, 1 << 20, blk, tile, fr) == \
        kkernel.gather_vmem_bytes(k, kkernel.RANK_SLAB, blk, tile, fr)


# ---------------------------------------------------------------------------
# Dispatch: gather preferred under its predicate, never silently dropped
# ---------------------------------------------------------------------------

def test_auto_prefers_gather_when_factors_fit():
    for nmodes, rank, fr in [(3, 128, 20_000), (4, 256, 50_000),
                             (5, 512, 20_000)]:
        assert kops.gather_fits_vmem(nmodes, rank, 512, 128, fr)
        got = kops.select_backend("auto", nmodes=nmodes, rank=rank,
                                  factor_rows=fr)
        assert got == "pallas_fused_gather", (nmodes, rank, fr)


def test_auto_degrades_gather_to_slab_streamed_then_fused():
    # Factor-resident overflows at full rank but one slab of each factor
    # fits -> slab-streamed gather keeps the in-kernel win.
    nmodes, rank, blk = 4, 8192, 512
    fr = 100_000
    assert not kops.gather_fits_vmem(nmodes, rank, blk, 128, fr)
    assert kops.gather_fits_vmem(nmodes, rank, blk, 128, fr, tiled=True)
    assert kops.select_backend(
        "auto", nmodes=nmodes, rank=rank, blk=blk,
        factor_rows=fr) == "pallas_fused_gather_tiled"
    # Factors too large for even one slab -> the materializing fused
    # family takes over, exactly as before the gather family existed.
    huge = 600_000_000
    assert not kops.gather_fits_vmem(nmodes, 128, blk, 128, huge,
                                     tiled=True)
    assert kops.select_backend(
        "auto", nmodes=nmodes, rank=128, blk=blk,
        factor_rows=huge) == "pallas_fused"


def test_auto_without_factor_rows_is_bit_identical_to_pr3():
    """A purely shape-keyed query (factor sizes unknown) must reproduce
    the pre-gather decisions exactly — the gather family is only ever
    chosen on certified residency."""
    for nmodes in (3, 4, 5):
        for rank in (4, 64, 256, 2048, 8192):
            for blk in (512, 2048):
                kw = dict(nmodes=nmodes, rank=rank, blk=blk, tile_rows=128)
                got = kops.select_backend("auto", **kw)
                if rank < kops.MIN_MXU_RANK:
                    want = "ref"
                elif kops.fused_fits_vmem(nmodes, rank, blk, 128):
                    want = "pallas_fused"
                elif kops.fused_fits_vmem(nmodes, rank, blk, 128,
                                          tiled=True):
                    want = "pallas_fused_tiled"
                else:
                    want = "ref"
                assert got == want, kw


def test_device_step_dispatch_no_silent_fallback():
    """End-to-end: mttkrp_device_step supplies factor_rows itself, so
    ``auto`` on a VMEM-eligible case must run the gather kernel — we
    prove it by matching the explicit gather backend bitwise (interpret
    mode makes each kernel's accumulation deterministic)."""
    shape = SHAPES[4]
    idx, val, factors = _sorted_case(shape, 150, 128, 0, seed=11)
    rows_cap = -(-shape[0] // TILE) * TILE
    fr = sum(shape[1:])
    assert kops.gather_fits_vmem(4, 128, BLK, TILE, fr)
    valid = np.ones(len(val), bool)
    auto = _device_step(idx, val, valid, factors, 0, rows_cap, "auto")
    explicit = _device_step(idx, val, valid, factors, 0, rows_cap,
                            "pallas_fused_gather")
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(explicit))


def test_table_cannot_pick_uncertifiable_gather():
    """A table that loves the gather backend may only steer onto it when
    the caller's factor_rows certifies VMEM residency."""
    entries = [
        tune.CalibrationEntry(
            nmodes=3, rank=r, blk=32, tile_rows=8, density=1.0,
            timings_s={"pallas_fused_gather": 0.001, "pallas_fused": 0.5,
                       "ref": 1.0}, factor_rows=128)
        for r in (128, 512)
    ]
    table = tune.CalibrationTable(entries=entries)
    kw = dict(nmodes=3, rank=128, blk=32, tile_rows=8)
    # certified: the table's preference is followed
    assert kops.select_backend("auto", table=table, factor_rows=1000,
                               **kw) == "pallas_fused_gather"
    # unknown factor sizes: discarded, static decision applies
    assert kops.select_backend("auto", table=table,
                               **kw) == "pallas_fused"
    # infeasible resident factor sizes: the preference is discarded just
    # the same; since PR 5 the static ladder then lands on the
    # out-of-core streamed gather (its bounded tile window fits at this
    # blk even though whole/slab residency cannot).
    assert kops.select_backend("auto", table=table,
                               factor_rows=600_000_000,
                               **kw) == kops.STREAM_BACKEND


# ---------------------------------------------------------------------------
# Runtime threading + tuned plans
# ---------------------------------------------------------------------------

def test_plan_for_gather_tiled_rank_slabs():
    rt = dist.DynasorRuntime(
        num_workers=1, nmodes=3, rank=512, rows_cap=(8, 8, 8),
        i_pad=(8, 8, 8), nnz_cap=8, bucket_cap=8, shape=(8, 8, 8))
    assert rt.plan_for(0, "pallas_fused_gather_tiled").rank_slabs == \
        kops.padded_rank(512) // kops.MXU_RANK_MULTIPLE == 4
    assert rt.plan_for(0, "pallas_fused_gather").rank_slabs == 1


def test_plan_modes_can_choose_gather_and_records_slabs():
    from repro.core.flycoo import build_flycoo
    t = random_sparse_tensor((40, 30, 20), 400, seed=3,
                             distribution="powerlaw")
    ft = build_flycoo(t, 4, m_bounds=(4, 16), g_bounds=(8, 64),
                      cache_bytes=1 << 20)
    entries = [
        tune.CalibrationEntry(
            nmodes=3, rank=r, blk=32, tile_rows=8, density=1.0,
            timings_s={"pallas_fused_gather_tiled": 0.001,
                       "pallas_fused": 1.0, "ref": 1.0}, factor_rows=128)
        for r in (128, 512)
    ]
    plans = tune.plan_modes(tune.CalibrationTable(entries=entries), ft, 512)
    assert plans is not None
    for p in plans:
        assert p.backend == "pallas_fused_gather_tiled"
        assert p.rank_slabs == kops.padded_rank(512) // \
            kops.MXU_RANK_MULTIPLE


# ---------------------------------------------------------------------------
# Schema back-compat: v2 tables (no factor_rows, no gather timings) load
# ---------------------------------------------------------------------------

def test_v2_calibration_table_still_loads():
    path = os.path.join(REPO_ROOT, "experiments", "tune", "fixtures",
                        "calibration_v2_example.json")
    table = tune.load_table(path)
    assert table.schema_version == tune.SCHEMA_VERSION
    assert table.meta.get("upgraded_from_schema") == 2
    assert table.entries
    for e in table.entries:
        assert e.factor_rows is None          # pre-v3: unrecorded
        assert not any(b.startswith("pallas_fused_gather")
                       for b in e.timings_s)
    # and the upgraded table still answers dispatch queries
    key = table.shape_keys()[0]
    nmodes, rank, blk, tile_rows = key
    got = kops.select_backend("auto", nmodes=nmodes, rank=rank, blk=blk,
                              tile_rows=tile_rows, table=table)
    assert got in kops.AUTO_BACKENDS + ("ref",)


def test_v3_round_trip_preserves_factor_rows(tmp_path):
    table = tune.calibrate(measure=tune.stub_measure, quick=True)
    for e in table.entries:
        assert e.factor_rows == (e.nmodes - 1) * 64
    path = table.save(str(tmp_path / "t.json"))
    loaded = tune.load_table(path)
    assert loaded.entries == table.entries
