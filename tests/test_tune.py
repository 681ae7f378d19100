"""repro.tune: calibration table, cost model, dispatch wiring, runtime plans.

All calibrations here use a *stubbed* measure function (deterministic
seconds as a function of backend × configuration) so the tests exercise
exactly the production table/model/dispatch code paths without timing
noise or interpret-mode Pallas runs.
"""
import json

import numpy as np
import pytest

from repro import tune
from repro.core import distributed as dist
from repro.core.flycoo import build_flycoo
from repro.core.remap import remap_capacities
from repro.core.tensors import random_sparse_tensor, zipf_4d
from repro.kernels.mttkrp import kernel as kkernel
from repro.kernels.mttkrp import ops as kops
from repro.oocore.planner import KRP_OPERANDS
from repro.tune.microbench import BACKENDS, GridPoint
from repro.tune.table import (SCHEMA_VERSION, CalibrationTable,
                              SchemaVersionError)

_OPS_BACKENDS = kops.BACKENDS
_AUTO_BACKENDS = kops.AUTO_BACKENDS


# The production traffic-model stub (CI tune-smoke uses it via
# `calibrate --stub`) doubles as the test fixture — one source for the
# pseudo-timing crossovers: segsum/ref win at small rank, the in-kernel
# gather family beats the materializing fused family, and the bf16
# compositions are fastest overall (to prove auto never picks them).
fake_measure = tune.stub_measure


@pytest.fixture()
def table():
    return tune.calibrate(measure=fake_measure, quick=True)


# ---------------------------------------------------------------------------
# Table serialization
# ---------------------------------------------------------------------------

def test_json_round_trip(table, tmp_path):
    path = table.save(str(tmp_path / "t.json"))
    loaded = tune.load_table(path)
    assert loaded.schema_version == SCHEMA_VERSION
    assert loaded.entries == table.entries
    assert loaded.meta == table.meta
    # argmin decisions survive the round trip at every key
    for key in table.shape_keys():
        n, r, b, t = key
        kw = dict(nmodes=n, rank=r, blk=b, tile_rows=t)
        assert loaded.best_backend(**kw) == table.best_backend(**kw)


def test_schema_version_rejected(table, tmp_path):
    path = table.save(str(tmp_path / "t.json"))
    obj = json.load(open(path))
    for bad in (SCHEMA_VERSION + 1, 0, None):
        obj["schema_version"] = bad
        json.dump(obj, open(path, "w"))
        with pytest.raises(SchemaVersionError, match="schema_version"):
            tune.load_table(path)


def test_find_table_skips_foreign_host(table, tmp_path):
    """A table calibrated on another machine must not steer this one."""
    foreign = CalibrationTable(
        entries=list(table.entries),
        meta=dict(table.meta, machine="tpu-v5e", jax_backend="tpu"))
    foreign.save(str(tmp_path / "foreign.json"))
    assert tune.find_table(str(tmp_path)) is None
    got = tune.find_table(str(tmp_path), match_host=False)  # explicit opt-in
    assert got is not None and got.entries == table.entries
    table.save(str(tmp_path / "local.json"))                # matching host
    assert tune.find_table(str(tmp_path)) is not None


def test_find_table_never_serves_stub_tables(table, tmp_path):
    """A `calibrate --stub` table saved to the registry path must not
    silently steer real dispatch: its pseudo-timings are a schema/CLI
    smoke artifact, loadable only by explicit path."""
    stub = CalibrationTable(entries=list(table.entries),
                            meta=dict(table.meta, stub=True))
    path = stub.save(str(tmp_path / "stubbed.json"))
    assert tune.find_table(str(tmp_path)) is None
    assert tune.find_table(str(tmp_path), match_host=False) is None
    assert tune.load_table(path).entries == table.entries  # explicit path ok
    table.save(str(tmp_path / "real.json"))
    found = tune.find_table(str(tmp_path))
    assert found is not None and not found.meta.get("stub")


def test_model_cache_invalidated_on_entry_change():
    t = _table_with_ranks((16,), lambda r: {"pallas_fused": 0.5, "ref": 0.1})
    kw = dict(nmodes=3, rank=16, blk=32, tile_rows=8)
    assert t.best_backend(**kw) == "ref"      # builds + caches the model
    t.entries.append(tune.CalibrationEntry(
        nmodes=3, rank=16, blk=32, tile_rows=8, density=4.0,
        timings_s={"pallas_fused": 0.01, "ref": 0.9}))
    assert t.best_backend(**kw) == "pallas_fused"  # cache rebuilt


def test_find_table_registry(table, tmp_path):
    assert tune.find_table(str(tmp_path / "missing")) is None
    # a corrupt file and a wrong-schema file are skipped, valid one found
    (tmp_path / "a_corrupt.json").write_text("{not json")
    bad = table.save(str(tmp_path / "b_wrongschema.json"))
    obj = json.load(open(bad))
    obj["schema_version"] = 999
    json.dump(obj, open(bad, "w"))
    table.save(str(tmp_path / "c_good.json"))
    found = tune.find_table(str(tmp_path))
    assert found is not None and found.entries == table.entries


# ---------------------------------------------------------------------------
# Cost model: interpolation
# ---------------------------------------------------------------------------

def _table_with_ranks(ranks, timings_fn):
    entries = [
        tune.CalibrationEntry(nmodes=3, rank=r, blk=32, tile_rows=8,
                              density=1.0, timings_s=timings_fn(r))
        for r in ranks
    ]
    return CalibrationTable(entries=entries)


def test_interpolation_at_off_grid_rank():
    # times linear in log2(rank) -> piecewise-linear interp is exact
    t = _table_with_ranks(
        (16, 64), lambda r: {"pallas_fused": 0.01 * np.log2(r),
                             "ref": 0.08 - 0.01 * np.log2(r)})
    m = t.model
    got = m.predict("pallas_fused", nmodes=3, rank=32, blk=32, tile_rows=8)
    assert got == pytest.approx(0.01 * 5.0)           # log2(32) = 5
    # crossover: pallas_fused wins below log2(r)=4, ref above
    assert t.best_backend(nmodes=3, rank=16, blk=32,
                          tile_rows=8) == "pallas_fused"
    assert t.best_backend(nmodes=3, rank=64, blk=32, tile_rows=8) == "ref"
    # clamped extrapolation beyond the knots
    assert m.predict("pallas_fused", nmodes=3, rank=1024, blk=32,
                     tile_rows=8) == pytest.approx(0.01 * 6.0)


def test_off_grid_shape_resolves_to_nearest_group():
    t = _table_with_ranks((16,), lambda r: {"pallas_fused": 0.5, "ref": 0.1})
    # different (blk, tile_rows) than any entry: nearest group answers
    assert t.best_backend(nmodes=3, rank=16, blk=512, tile_rows=128) == "ref"
    # different nmodes too
    assert t.best_backend(nmodes=5, rank=16, blk=512, tile_rows=128) == "ref"


# ---------------------------------------------------------------------------
# Dispatch wiring: select_backend(table=...)
# ---------------------------------------------------------------------------

def test_select_backend_matches_measured_argmin_on_grid(table):
    """Acceptance: table-driven auto == measured best on EVERY grid key
    (argmin over the numerics-preserving AUTO_BACKENDS — never bf16).
    ``factor_rows`` comes from the measured case (as ``repro.tune
    check`` supplies it), so a measured-fast gather backend is a
    certifiable choice."""
    for key in table.shape_keys():
        n, r, b, t = key
        agg = {
            bk: float(np.median([e.timings_s[bk] for e in table.entries
                                 if e.shape_key == key]))
            for bk in BACKENDS
        }
        want = min(sorted(_AUTO_BACKENDS), key=lambda bk: (agg[bk], bk))
        got = kops.select_backend("auto", nmodes=n, rank=r, blk=b,
                                  tile_rows=t, table=table,
                                  factor_rows=tune.key_factor_rows(
                                      table, key))
        assert got == want, (key, got, want)


def test_select_backend_without_table_is_static(table):
    """No table (or an unanswerable one) -> bit-identical static choices."""
    empty = CalibrationTable(entries=[])
    for nmodes in (2, 3, 4, 5):
        for rank in (4, 16, 64, 256, 2048, 8192):
            for blk in (512, 2048):
                kw = dict(nmodes=nmodes, rank=rank, blk=blk, tile_rows=128)
                static = kops.select_backend("auto", **kw)
                # reimplementation of the documented static rule
                rpad = kops.padded_rank(rank)
                if rank < kops.MIN_MXU_RANK:
                    want = "ref"
                elif kkernel.fused_vmem_bytes(
                        KRP_OPERANDS, rpad, blk, 128) <= \
                        kops.VMEM_BUDGET_BYTES:
                    want = "pallas_fused"
                elif kkernel.fused_tiled_vmem_bytes(
                        KRP_OPERANDS, rpad, blk, 128) <= \
                        kops.VMEM_BUDGET_BYTES:
                    want = "pallas_fused_tiled"
                else:
                    want = "ref"
                assert static == want
                assert kops.select_backend(
                    "auto", table=empty, **kw) == static


def test_select_backend_table_never_returns_segsum_or_bf16(table):
    # segsum is always fastest under fake_measure at rank 16 and bf16 is
    # fastest everywhere, but ops cannot run the former and auto must
    # not change numerics via the latter -- the table path restricts to
    # the numerics-preserving ops backends.
    for key in table.shape_keys():
        n, r, b, t = key
        got = kops.select_backend("auto", nmodes=n, rank=r, blk=b,
                                  tile_rows=t, table=table)
        assert got in _AUTO_BACKENDS


def test_explicit_backend_ignores_table(table):
    for bk in _OPS_BACKENDS:
        assert kops.select_backend(bk, nmodes=3, rank=16, table=table) == bk


def test_below_grid_rank_keeps_static_mxu_guard(table):
    """A table whose grid starts at rank 16 must not override the
    static rank<8 -> ref rule via clamped below-grid extrapolation."""
    for rank in (2, 4, 7):
        kw = dict(nmodes=3, rank=rank, blk=32, tile_rows=8)
        assert not table.covers(**kw)
        assert kops.select_backend("auto", table=table, **kw) == "ref"
    # ...but a rank the table actually measured answers from measurements
    low = CalibrationTable(entries=[tune.CalibrationEntry(
        nmodes=3, rank=4, blk=32, tile_rows=8, density=1.0,
        timings_s={"pallas_fused": 0.001, "ref": 0.5})])
    assert low.covers(nmodes=3, rank=4, blk=32, tile_rows=8)
    assert kops.select_backend("auto", nmodes=3, rank=4, blk=32,
                               tile_rows=8, table=low) == "pallas_fused"
    # plan_modes applies the same guard
    _, ft = _small_ft()
    plans = tune.plan_modes(table, ft, 4)
    assert plans is not None
    assert all(p.backend in ("ref", "segsum") for p in plans)


def test_table_cannot_pick_infeasible_fused():
    """VMEM feasibility is a hard constraint even when the table loves
    pallas_fused: extrapolating far beyond the measured grid must not
    select a fused working set that exceeds the budget. (The static
    fallback it lands on is now the rank-tiled kernel, whose slabbed
    working set always fits — the PR-2 rule fell all the way back to
    the materialized path here.)"""
    t = _table_with_ranks(
        (16, 256), lambda r: {"pallas_fused": 0.001, "ref": 1.0})
    kw = dict(nmodes=5, rank=16384, blk=512, tile_rows=128)
    assert kkernel.fused_vmem_bytes(
        KRP_OPERANDS, kops.padded_rank(16384), 512,
        128) > kops.VMEM_BUDGET_BYTES
    got = kops.select_backend("auto", table=t, **kw)
    assert got == kops.select_backend("auto", **kw) == "pallas_fused_tiled"
    # ...and plan_modes applies the same guard per candidate shape
    entries = [tune.CalibrationEntry(nmodes=3, rank=r, blk=512,
                                     tile_rows=128, density=1.0,
                                     timings_s={"pallas_fused": 0.001,
                                                "ref": 1.0})
               for r in (16, 256)]
    _, ft = _small_ft()          # 3-mode: fused needs rank 16384 to overflow
    assert kkernel.fused_vmem_bytes(
        KRP_OPERANDS, kops.padded_rank(16384), 512,
        128) > kops.VMEM_BUDGET_BYTES
    plans = tune.plan_modes(CalibrationTable(entries=entries), ft, 16384)
    assert plans is not None
    assert all(p.backend != "pallas_fused" for p in plans)


def test_table_cannot_pick_removed_pallas_rung():
    """Tables written before the ``pallas`` backend was folded into
    ``pallas_fused`` may still time it. A table that loves it at a rank
    where the untiled operand block overflows VMEM must land on the
    static decision, as one that loves ``pallas_fused`` does."""
    t = _table_with_ranks(
        (16, 256), lambda r: {"pallas": 0.0005, "pallas_fused": 0.001,
                              "ref": 1.0})
    assert "pallas" not in kops.BACKENDS
    kw = dict(nmodes=5, rank=16384, blk=512, tile_rows=128)
    assert t.best_backend(**kw) == "pallas"     # the raw table argmin
    got = kops.select_backend("auto", table=t, **kw)
    assert got == kops.select_backend("auto", **kw) == "pallas_fused_tiled"
    with pytest.raises(ValueError, match="unknown MTTKRP backend"):
        kops.select_backend("pallas", nmodes=5, rank=16)
    entries = [tune.CalibrationEntry(nmodes=3, rank=r, blk=512,
                                     tile_rows=128, density=1.0,
                                     timings_s={"pallas": 0.0005,
                                                "pallas_fused": 0.001})
               for r in (16, 256)]
    _, ft = _small_ft()
    # Neither measured backend may be planned, so the table cannot
    # answer and the runtime keeps its static configuration.
    assert tune.plan_modes(CalibrationTable(entries=entries), ft,
                           16384) is None


# ---------------------------------------------------------------------------
# Runtime wiring: bucket_caps + mode plans
# ---------------------------------------------------------------------------

def _small_ft(seed=3):
    t = random_sparse_tensor((40, 30, 20), 400, seed=seed,
                             distribution="powerlaw")
    return t, build_flycoo(t, 4, m_bounds=(4, 16), g_bounds=(8, 64),
                           cache_bytes=1 << 20)


def test_prepare_runtime_per_transition_caps():
    _, ft = _small_ft()
    rt, _ = dist.prepare_runtime(ft, rank=8, tile_rows=8)
    caps = remap_capacities(ft)
    assert rt.bucket_caps == tuple(caps)
    assert rt.bucket_cap == max(caps)
    for n in range(ft.nmodes):
        assert rt.bucket_cap_for(n) == caps[n] <= rt.bucket_cap


def test_prepare_runtime_uniform_cap_escape_hatch():
    _, ft = _small_ft()
    rt, _ = dist.prepare_runtime(ft, rank=8, tile_rows=8, uniform_cap=True)
    assert rt.bucket_caps is None
    for n in range(ft.nmodes):
        assert rt.bucket_cap_for(n) == rt.bucket_cap


def test_runtime_back_compat_construction():
    # direct construction without the new fields (old call sites) works
    rt = dist.DynasorRuntime(
        num_workers=1, nmodes=3, rank=8, rows_cap=(8, 8, 8),
        i_pad=(8, 8, 8), nnz_cap=8, bucket_cap=8, shape=(8, 8, 8))
    assert rt.bucket_cap_for(2) == 8
    assert rt.plan_for(1, "pallas_fused") == dist.ModePlan("pallas_fused",
                                                          512, 128)


def test_prepare_runtime_with_table_builds_plans(table):
    _, ft = _small_ft()
    rt, (idx, val, mask) = dist.prepare_runtime(ft, rank=16, table=table)
    assert rt.mode_plans is not None and len(rt.mode_plans) == ft.nmodes
    for n, plan in enumerate(rt.mode_plans):
        assert plan.backend in BACKENDS
        # grid shapes only: quick grid is blk=32, tile_rows=8
        assert (plan.blk, plan.tile_rows) == (32, 8)
        # rows_cap rounded to the tuned tile
        assert rt.rows_cap[n] % plan.tile_rows == 0
        # auto follows the plan; explicit backend overrides it
        assert rt.plan_for(n, "auto") == plan
        assert rt.plan_for(n, "segsum").backend == "segsum"
    assert idx.shape[0] == ft.params.num_workers


def test_plan_modes_unanswerable_returns_none():
    _, ft = _small_ft()
    assert tune.plan_modes(CalibrationTable(entries=[]), ft, 16) is None
    rt, _ = dist.prepare_runtime(ft, rank=16,
                                 table=CalibrationTable(entries=[]))
    assert rt.mode_plans is None          # static configuration kept


# ---------------------------------------------------------------------------
# zipf_4d generator (satellite fix)
# ---------------------------------------------------------------------------

def test_zipf_4d_keeps_nnz_and_uniqueness():
    shape, nnz = (150, 140, 600, 30), 4000
    t = zipf_4d(shape, nnz, seed=0)
    assert t.nnz == nnz
    flat = np.ravel_multi_index(tuple(t.indices.T), shape)
    assert len(np.unique(flat)) == nnz    # rejection worked: no duplicates
    # where the old power-law generator collapses
    old = random_sparse_tensor(shape, nnz, seed=0, distribution="powerlaw")
    assert old.nnz < nnz // 10


def test_zipf_4d_is_actually_skewed():
    shape, nnz = (200, 180, 500, 40), 5000
    t = zipf_4d(shape, nnz, seed=1)
    counts = np.sort(np.bincount(t.indices[:, 0], minlength=shape[0]))
    top_share = counts[-shape[0] // 100:].sum() / nnz
    u = random_sparse_tensor(shape, nnz, seed=1, distribution="uniform")
    uc = np.sort(np.bincount(u.indices[:, 0], minlength=shape[0]))
    u_share = uc[-shape[0] // 100:].sum() / u.nnz
    assert top_share > 3 * u_share        # hubs exist


def test_zipf_4d_rejects_impossible_nnz():
    with pytest.raises(ValueError, match="capacity"):
        zipf_4d((2, 2, 2, 2), 17, seed=0)
