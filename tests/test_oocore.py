"""repro.oocore — out-of-core spMTTKRP (PR-5 tentpole).

Coverage per the issue checklist:
  * ``fused_mttkrp_nmode_gather_stream`` bit-exact vs the resident
    ``pallas_fused_gather`` on fp32 across N ∈ {3, 4, 5}, including a
    forced multi-chunk execution through the ``oocore`` executor, plus
    the bf16 composition;
  * hypothesis property sweeps: (a) streamed ≡ resident bit-exact for
    random chunk/row-tile splits, (b) ``ResidencyPlan`` invariants —
    every factor row covered exactly once by the tile spans, the budget
    respected, and the plan monotone in the budget;
  * dispatch: ``select_backend`` / ``plan_modes`` route through
    ``plan_residency`` and choose the streaming backend only when
    whole/slab residency fails; ``ModePlan`` threads the window
    geometry;
  * tune schema v4: stream timings + ``stream_window_tiles`` recorded,
    committed v3 fixture still loads (back-compat window 1–3);
  * the ``tile_schedule`` correctness contract and the stream VMEM
    formula;
  * the legacy 3-mode kernel entry is a warning deprecated alias.
"""
import os

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import tune
from repro.core import distributed as dist
from repro.core.tensors import random_sparse_tensor
from repro.kernels.mttkrp import kernel as kkernel
from repro.kernels.mttkrp import ops as kops
from repro.oocore import planner
from repro.oocore.executor import chunk_boundaries, mttkrp_out_of_core

BLK, TILE = 32, 8

# Mode-0 output; the *input* factors span multiple FACTOR_ROW_TILE tiles
# so the stream kernel actually pages tiles (not the degenerate 1-tile
# window).
SHAPES = {3: (20, 300, 170), 4: (12, 300, 170, 6), 5: (8, 300, 170, 6, 5)}

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


# ---------------------------------------------------------------------------
# Golden: streamed gather ≡ resident gather, bit-exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nmodes", [3, 4, 5])
@pytest.mark.parametrize("rank", [128, 256])
def test_stream_bitexact_vs_resident(nmodes, rank):
    """The stream kernel's windowed tiles hold exactly the rows the
    resident kernel gathers, so the arithmetic (and its order) is
    unchanged — bitwise agreement, not tolerance."""
    shape = SHAPES[nmodes]
    idx, val, factors = _sorted_case(shape, 150, rank, 0, seed=nmodes)
    rows_cap = -(-shape[0] // TILE) * TILE
    valid = np.ones(len(val), bool)
    resident = _device_step(idx, val, valid, factors, 0, rows_cap,
                            "pallas_fused_gather")
    streamed = _device_step(idx, val, valid, factors, 0, rows_cap,
                            "pallas_fused_gather_stream")
    np.testing.assert_array_equal(np.asarray(streamed), np.asarray(resident))


def test_stream_multichunk_forced_bitexact():
    """A working-set budget small enough to force many chunks must not
    change a single bit: the executor threads the accumulator through
    out_init, re-bracketing the same additions in the same order."""
    shape = SHAPES[4]
    idx, val, factors = _sorted_case(shape, 250, 256, 0, seed=9)
    rows_cap = -(-shape[0] // TILE) * TILE
    valid = np.arange(len(val)) < len(val) - 7       # trailing invalids
    val = np.where(valid, val, 0.0).astype(np.float32)
    resident = _device_step(idx, val, valid, factors, 0, rows_cap,
                            "pallas_fused_gather")
    out, stats = mttkrp_out_of_core(
        idx, val, valid, factors, mode=0, rows_cap=rows_cap, blk=BLK,
        tile_rows=TILE, max_chunk_bytes=1500)
    assert stats.chunks >= 3, stats.chunks
    np.testing.assert_array_equal(np.asarray(out), np.asarray(resident))
    # counted traffic is self-consistent
    assert stats.distinct_tile_bytes <= stats.scheduled_tile_bytes
    assert stats.pipelined_tile_bytes <= stats.scheduled_tile_bytes
    assert stats.window_vmem_bytes < stats.resident_equiv_vmem_bytes


def test_stream_bf16_composition_bitexact_vs_resident_bf16():
    shape = SHAPES[4]
    idx, val, factors = _sorted_case(shape, 150, 128, 0, seed=5)
    rows_cap = -(-shape[0] // TILE) * TILE
    valid = np.ones(len(val), bool)
    want = _device_step(idx, val, valid, factors, 0, rows_cap,
                        "pallas_fused_gather", gather_dtype="bfloat16")
    got = _device_step(idx, val, valid, factors, 0, rows_cap,
                       "pallas_fused_gather_stream", gather_dtype="bfloat16")
    assert np.asarray(got).dtype == np.float32       # fp32 accumulate
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=12, deadline=None)
@given(
    seed=st.integers(0, 10_000),
    nnz=st.integers(40, 260),
    rank=st.sampled_from([128, 256]),
    tile_rows=st.sampled_from([8, 16]),
    blk=st.sampled_from([16, 32]),
    max_chunk_bytes=st.one_of(st.none(), st.integers(600, 20_000)),
)
def test_stream_chunk_split_property(seed, nnz, rank, tile_rows, blk,
                                     max_chunk_bytes):
    """(a) streamed ≡ resident, bit-exact on fp32, for random chunk /
    row-tile splits — the issue's property sweep."""
    shape = (40, 300, 170)
    idx, val, factors = _sorted_case(shape, nnz, rank, 0, seed=seed)
    rows_cap = -(-shape[0] // tile_rows) * tile_rows
    valid = np.ones(len(val), bool)
    resident = kops.mttkrp_device_step(
        jnp.asarray(idx), jnp.asarray(val), jnp.asarray(valid), factors,
        mode=0, rows_cap=rows_cap, row_offset=0, blk=blk,
        tile_rows=tile_rows, interpret=True, backend="pallas_fused_gather")
    out, _ = mttkrp_out_of_core(
        idx, val, valid, factors, mode=0, rows_cap=rows_cap, blk=blk,
        tile_rows=tile_rows, max_chunk_bytes=max_chunk_bytes)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(resident))


def test_chunk_boundaries_cover_and_prefer_tile_edges():
    tiles = np.array([0, 0, 0, 1, 1, 2, 2, 2, 2, 3])
    bounds = chunk_boundaries(tiles, 4)
    # exact cover, in order
    assert bounds[0][0] == 0 and bounds[-1][1] == len(tiles)
    for (a, b), (c, _) in zip(bounds, bounds[1:]):
        assert b == c and a < b
    # boundaries land on tile edges when a tile run fits the budget
    for _, stop in bounds[:-1]:
        assert tiles[stop] != tiles[stop - 1]
    # a run longer than the budget must still split (mid-tile)
    long_run = np.zeros(10, int)
    assert [b - a for a, b in chunk_boundaries(long_run, 4)] == [4, 4, 2]


# ---------------------------------------------------------------------------
# tile_schedule contract
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), blk=st.sampled_from([8, 16, 32]),
       rows=st.integers(1, 2000), blocks=st.integers(1, 6))
def test_tile_schedule_holds_every_touched_tile(seed, blk, rows, blocks):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, rows, size=blocks * blk).astype(np.int32)
    window = planner.stream_window_tiles(blk, rows)
    sched = np.asarray(kops.tile_schedule(jnp.asarray(idx), blk, window))
    assert sched.shape == (blocks, window)
    frow = kkernel.FACTOR_ROW_TILE
    for b in range(blocks):
        touched = set(idx[b * blk:(b + 1) * blk] // frow)
        assert touched <= set(sched[b]), (b, touched, sched[b])
        # and nothing out of range is ever scheduled
        assert set(sched[b]) <= set(idx[b * blk:(b + 1) * blk] // frow)


def test_gather_stream_vmem_bytes_formula():
    k, rpad, blk, tile, windows = 3, 512, 32, 8, (5, 3, 1)
    got = kkernel.gather_stream_vmem_bytes(k, rpad, blk, tile, windows)
    slab = kkernel.RANK_SLAB
    window_term = sum(w * kkernel.FACTOR_ROW_TILE * slab * 4
                      for w in windows)
    # The tile schedules live in SMEM via scalar prefetch (the body
    # reads them scalar-by-scalar) so, like tile_of_block, they add no
    # VMEM term.
    base = kkernel.fused_vmem_bytes(0, slab, blk, tile,
                                    index_stream_modes=k)
    assert got == window_term + base
    # independent of the factor sizes and (past one slab) of R
    assert kkernel.gather_stream_vmem_bytes(k, 1 << 16, blk, tile,
                                            windows) == got
    # bf16 halves exactly the window term
    bf16 = kkernel.gather_stream_vmem_bytes(k, rpad, blk, tile, windows,
                                            gather_itemsize=2)
    assert got - bf16 == window_term // 2


# ---------------------------------------------------------------------------
# ResidencyPlan invariants (issue property sweep (b))
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(
    nmodes=st.integers(3, 5),
    rank=st.sampled_from([8, 64, 128, 512, 4096]),
    blk=st.sampled_from([16, 32, 512]),
    tile_rows=st.sampled_from([8, 128]),
    rows=st.lists(st.integers(1, 2_000_000), min_size=2, max_size=4),
    budget_mb=st.integers(1, 256),
)
def test_residency_plan_invariants(nmodes, rank, blk, tile_rows, rows,
                                   budget_mb):
    rows = tuple(rows[:nmodes - 1]) + (64,) * max(0, nmodes - 1 - len(rows))
    budget = budget_mb << 20
    plan = planner.plan_residency(
        nmodes=nmodes, rank=rank, blk=blk, tile_rows=tile_rows,
        factor_rows=rows, vmem_budget=budget)
    assert plan.backend in kops.BACKENDS
    # budget respected: every rung fits, the last resort (ref) holds
    # nothing in VMEM
    assert plan.fits
    assert plan.vmem_bytes <= budget
    # every factor row covered exactly once by the tile spans
    for f in plan.factors:
        spans = f.tile_spans()
        assert spans[0][0] == 0 and spans[-1][1] == f.rows
        for (a, b), (c, _) in zip(spans, spans[1:]):
            assert b == c and a < b
        assert 1 <= f.window_tiles <= f.row_tiles
        if f.policy == "stream":
            assert f.window_tiles == planner.stream_window_tiles(blk, f.rows)
            assert f.window_tiles < f.row_tiles
    if plan.streams:
        assert plan.window_tiles and len(plan.window_tiles) == nmodes - 1


@pytest.mark.parametrize("nmodes", [3, 4, 5])
def test_residency_ladder_ends_in_ref(nmodes):
    """Below one rank slab of the tiled rung no Pallas rung fits: the
    plan is the XLA segment sum, which holds nothing in VMEM. One byte
    more and the tiled rung is back."""
    slab = kkernel.fused_tiled_vmem_bytes(planner.KRP_OPERANDS, 256, 512,
                                          128)
    kw = dict(nmodes=nmodes, rank=256, blk=512, tile_rows=128)
    plan = planner.plan_residency(vmem_budget=slab - 1, **kw)
    assert (plan.backend, plan.vmem_bytes, plan.fits) == ("ref", 0, True)
    plan = planner.plan_residency(vmem_budget=slab, **kw)
    assert (plan.backend, plan.vmem_bytes, plan.fits) == (
        "pallas_fused_tiled", slab, True)


@settings(max_examples=20, deadline=None)
@given(
    nmodes=st.integers(3, 5),
    rank=st.sampled_from([64, 128, 512]),
    blk=st.sampled_from([16, 32, 512]),
    rows=st.integers(100, 5_000_000),
    b1=st.integers(1, 512),
    b2=st.integers(1, 512),
)
def test_residency_plan_monotone_in_budget(nmodes, rank, blk, rows, b1, b2):
    """Growing the budget may only move the decision toward earlier
    (more-resident) rungs of the ladder — never the reverse."""
    lo, hi = sorted((b1, b2))
    # rank >= MIN_MXU_RANK here, so ref is only the last resort
    order = ["pallas_fused_gather", "pallas_fused_gather_tiled",
             planner.STREAM_BACKEND, "pallas_fused", "pallas_fused_tiled",
             "ref"]
    p_lo = planner.plan_residency(nmodes=nmodes, rank=rank, blk=blk,
                                  tile_rows=8, factor_rows=rows,
                                  vmem_budget=lo << 20)
    p_hi = planner.plan_residency(nmodes=nmodes, rank=rank, blk=blk,
                                  tile_rows=8, factor_rows=rows,
                                  vmem_budget=hi << 20)
    assert order.index(p_hi.backend) <= order.index(p_lo.backend)


# ---------------------------------------------------------------------------
# Dispatch: the streaming rung fires only when whole/slab residency fails
# ---------------------------------------------------------------------------

def test_auto_streams_only_when_residency_fails():
    # rank 512: whole residency costs rows·512·4 B, one slab rows·128·4 B,
    # so the whole/slab/stream rungs separate cleanly.
    kw = dict(nmodes=3, rank=512, blk=32, tile_rows=8)
    # resident fits -> resident gather, not stream
    assert kops.select_backend("auto", factor_rows=1_000,
                               **kw) == "pallas_fused_gather"
    # whole fails, slab fits -> slab-streamed, not out-of-core
    big = 80_000
    assert not kops.gather_fits_vmem(3, 512, 32, 8, big)
    assert kops.gather_fits_vmem(3, 512, 32, 8, big, tiled=True)
    assert kops.select_backend("auto", factor_rows=big,
                               **kw) == "pallas_fused_gather_tiled"
    # whole and slab both fail, window fits -> the out-of-core rung
    huge = 600_000_000
    assert not kops.gather_fits_vmem(3, 512, 32, 8, huge, tiled=True)
    assert kops.gather_stream_fits_vmem(3, 512, 32, 8, huge)
    assert kops.select_backend("auto", factor_rows=huge,
                               **kw) == kops.STREAM_BACKEND
    # window overflows too (shard-sized blocks) -> fused, as before PR 5
    assert not kops.gather_stream_fits_vmem(4, 128, 512, 128, huge)
    assert kops.select_backend("auto", nmodes=4, rank=128, blk=512,
                               tile_rows=128,
                               factor_rows=huge) == "pallas_fused"
    # no factor knowledge -> never the gather family at all
    assert kops.select_backend("auto", **kw) == "pallas_fused"


def test_select_backend_matches_planner_ladder():
    """select_backend's static decision IS plan_residency's backend."""
    for nmodes in (3, 4, 5):
        for rank in (4, 64, 256, 2048):
            for blk in (16, 512):
                for fr in (None, 1_000, 300_000, 600_000_000):
                    kw = dict(nmodes=nmodes, rank=rank, blk=blk,
                              tile_rows=8, factor_rows=fr)
                    assert kops.select_backend("auto", **kw) == \
                        planner.plan_residency(**kw).backend, kw


def test_device_step_auto_streams_under_tiny_budget_geometry():
    """End-to-end: mttkrp_device_step supplies per-mode factor_rows, so
    an explicitly requested stream backend matches ``auto``'s choice
    whenever the planner picks streaming — proven bitwise."""
    shape = SHAPES[3]
    idx, val, factors = _sorted_case(shape, 150, 128, 0, seed=2)
    rows_cap = -(-shape[0] // TILE) * TILE
    valid = np.ones(len(val), bool)
    auto = _device_step(idx, val, valid, factors, 0, rows_cap, "auto")
    explicit = _device_step(idx, val, valid, factors, 0, rows_cap,
                            "pallas_fused_gather_stream")
    # the small case resolves to the resident gather; both must agree
    # bitwise anyway (the stream kernel is bit-exact by contract)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(explicit))


# ---------------------------------------------------------------------------
# Runtime threading + tuned plans
# ---------------------------------------------------------------------------

def test_plan_for_stream_backend_records_slabs_and_windows():
    rt = dist.DynasorRuntime(
        num_workers=2, nmodes=3, rank=512, rows_cap=(8, 400, 300),
        i_pad=(16, 800, 600), nnz_cap=8, bucket_cap=8, shape=(16, 800, 600),
        blk=32)
    p = rt.plan_for(0, "pallas_fused_gather_stream")
    assert p.rank_slabs == kops.padded_rank(512) // kops.MXU_RANK_MULTIPLE
    assert p.window_tiles == (
        planner.stream_window_tiles(32, 800),
        planner.stream_window_tiles(32, 600))
    # non-stream backends carry no window metadata
    assert rt.plan_for(0, "pallas_fused_gather").window_tiles == ()


def test_plan_modes_can_choose_stream_and_records_geometry():
    from repro.core.flycoo import build_flycoo
    t = random_sparse_tensor((40, 30, 20), 400, seed=3,
                             distribution="powerlaw")
    ft = build_flycoo(t, 4, m_bounds=(4, 16), g_bounds=(8, 64),
                      cache_bytes=1 << 20)
    entries = [
        tune.CalibrationEntry(
            nmodes=3, rank=r, blk=32, tile_rows=8, density=1.0,
            timings_s={"pallas_fused_gather_stream": 0.001,
                       "pallas_fused": 1.0, "ref": 1.0},
            factor_rows=128, stream_window_tiles=1)
        for r in (128, 512)
    ]
    plans = tune.plan_modes(tune.CalibrationTable(entries=entries), ft, 512)
    assert plans is not None
    for n, p in enumerate(plans):
        assert p.backend == "pallas_fused_gather_stream"
        assert p.rank_slabs == kops.padded_rank(512) // \
            kops.MXU_RANK_MULTIPLE
        assert len(p.window_tiles) == ft.nmodes - 1
        assert all(w >= 1 for w in p.window_tiles)


# ---------------------------------------------------------------------------
# Schema v4 + v3 back-compat
# ---------------------------------------------------------------------------

def test_v3_calibration_fixture_still_loads():
    path = os.path.join(REPO_ROOT, "experiments", "tune", "fixtures",
                        "calibration_v3_example.json")
    table = tune.load_table(path)
    assert table.schema_version == tune.SCHEMA_VERSION == 4
    assert table.meta.get("upgraded_from_schema") == 3
    assert table.entries
    for e in table.entries:
        assert e.factor_rows is not None          # v3 recorded it
        assert e.stream_window_tiles is None      # pre-v4: unrecorded
        assert "pallas_fused_gather_stream" not in e.timings_s
    key = table.shape_keys()[0]
    nmodes, rank, blk, tile_rows = key
    got = kops.select_backend("auto", nmodes=nmodes, rank=rank, blk=blk,
                              tile_rows=tile_rows, table=table)
    assert got in kops.AUTO_BACKENDS + ("ref",)


def test_v4_round_trip_records_stream_fields(tmp_path):
    table = tune.calibrate(measure=tune.stub_measure, quick=True)
    for e in table.entries:
        assert "pallas_fused_gather_stream" in e.timings_s
        assert e.stream_window_tiles == 1         # 64-row side factors
    path = table.save(str(tmp_path / "t.json"))
    loaded = tune.load_table(path)
    assert loaded.entries == table.entries
    assert loaded.schema_version == 4

