"""Microbenchmark harness: measure every MTTKRP backend on a config grid.

Each grid point is a synthetic per-device mode step — a sorted,
power-law-skewed nonzero stream of the requested density plus random
factor matrices — timed through every backend:

  * the ``kernels.mttkrp.ops.BACKENDS`` family (``pallas_fused``,
    ``pallas_fused_tiled``, ``pallas_fused_bf16``, the
    in-kernel-gather ``pallas_fused_gather`` trio, the out-of-core
    ``pallas_fused_gather_stream``, ``ref``) via ``mttkrp_device_step``
    (interpret mode on CPU — the timings rank the backends' *emulated*
    cost; on a real TPU the same harness calibrates compiled kernels);
  * ``segsum`` — the plain-XLA segment-sum path used by
    ``core.distributed.device_mttkrp``.

The bf16-gather timings are recorded like any other backend but the
``auto`` dispatch never follows them (numerics opt-in — see
``ops.AUTO_BACKENDS``); they exist so ``repro.tune show`` / the bench
suite can report what explicit bf16 opt-in would buy. Every v3 entry
also records ``factor_rows`` (see :func:`case_factor_rows`) so the
dispatch can certify the gather family's VMEM feasibility when
following the table.

The ``measure`` hook is injectable (``measure(backend, point) ->
seconds``) so tests calibrate with deterministic stub timings and the
table/selection logic stays exactly the code path production uses.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensors import _powerlaw_columns
from ..kernels.mttkrp import ops as kops
from ..obs import counters as _obs
from ..obs import tracer as _tracer_mod
from .table import CalibrationEntry, CalibrationTable, host_meta

__all__ = [
    "BACKENDS",
    "GridPoint",
    "default_grid",
    "make_case",
    "case_factor_rows",
    "case_stream_window_tiles",
    "stub_measure",
    "calibrate",
]

# Everything the microbench times: the ops-runnable backends + the
# distributed layer's plain-XLA segsum path.
BACKENDS = kops.BACKENDS + ("segsum",)

# Dimension of the non-output modes in a synthetic case (gather breadth).
_SIDE_DIM = 64
# Output row tiles per case: rows_cap = _NUM_TILES * tile_rows.
_NUM_TILES = 4


@dataclasses.dataclass(frozen=True)
class GridPoint:
    """One microbenchmark configuration."""

    nmodes: int
    rank: int
    blk: int
    tile_rows: int
    density: float          # mean nonzeros per (blk × row-tile) block


def default_grid(quick: bool = True) -> list[GridPoint]:
    """The calibration grid; ``quick`` keeps interpret-mode runs short."""
    if quick:
        nmodes, ranks = (3, 4), (16, 128)
        blks, tiles, densities = (32,), (8,), (0.5, 2.0)
    else:
        # rank 512 = 4 rank slabs: the full grid actually exercises the
        # tiled kernel's slab loop, so its knots aren't extrapolations.
        nmodes, ranks = (3, 4, 5), (16, 32, 64, 128, 256, 512)
        blks, tiles, densities = (32, 128), (8, 16), (0.25, 1.0, 4.0)
    return [
        GridPoint(nmodes=n, rank=r, blk=b, tile_rows=t, density=d)
        for n in nmodes for r in ranks for b in blks for t in tiles
        for d in densities
    ]


def make_case(point: GridPoint, *, seed: int = 0):
    """Synthetic sorted stream + factors for one grid point.

    Returns ``(idx, val, valid, factors, rows_cap)`` with output mode 0:
    ``density`` sets the nonzero count per output-row tile relative to
    ``blk``, and rows are power-law skewed (hub structure, like the
    FROSTT tensors the dispatch will face).
    """
    rng = np.random.default_rng(seed)
    rows_cap = _NUM_TILES * point.tile_rows
    nnz = max(8, int(point.density * _NUM_TILES * point.blk))
    # Truncated-Pareto skew, same draw as the tensor generators.
    rows = np.sort(_powerlaw_columns(rng, (rows_cap,), nnz, 1.3)[:, 0])
    cols = [rows] + [rng.integers(0, _SIDE_DIM, size=nnz)
                     for _ in range(point.nmodes - 1)]
    idx = jnp.asarray(np.stack(cols, axis=1).astype(np.int32))
    val = jnp.asarray(rng.standard_normal(nnz).astype(np.float32))
    valid = jnp.ones((nnz,), bool)
    dims = (rows_cap,) + (_SIDE_DIM,) * (point.nmodes - 1)
    factors = [jnp.asarray(rng.standard_normal((d, point.rank)), jnp.float32)
               for d in dims]
    return idx, val, valid, factors, rows_cap


def case_factor_rows(point: GridPoint) -> int:
    """Total input-factor rows of :func:`make_case`'s synthetic case.

    The non-output modes all have ``_SIDE_DIM`` rows, so this is the
    resident set the in-kernel gather backends hold; it is recorded in
    every v3 calibration entry so the dispatch can check gather
    feasibility when following the table.
    """
    return (point.nmodes - 1) * _SIDE_DIM


def case_stream_window_tiles(point: GridPoint) -> int:
    """Per-input-mode stream-window width of the synthetic case.

    What ``pallas_fused_gather_stream`` holds in VMEM per mode when it
    runs the case: the ``repro.oocore`` planner's correctness bound for
    ``_SIDE_DIM``-row factors at this block size. Recorded in every v4
    calibration entry so a stream timing carries its window context.
    """
    from ..oocore.planner import stream_window_tiles

    return stream_window_tiles(point.blk, _SIDE_DIM)


def stub_measure(backend: str, point: GridPoint) -> float:
    """Deterministic pseudo-timings from the traffic model (no kernels run).

    For schema/CLI smoke runs (``python -m repro.tune calibrate --stub``
    in CI) and anywhere a full interpret-mode calibration is too slow:
    the relative ordering mirrors the counted per-nonzero HBM traffic of
    each backend (gather < fused, bf16 halving gather
    bytes, segment-sum paths winning at small rank), so the resulting
    table exercises exactly the production table/model/dispatch code
    paths with self-consistent argmins.
    """
    k = (point.nmodes - 1) * point.rank * (1.0 + 0.1 * point.density)
    return {
        "ref": 8e-4 * point.rank,
        "segsum": 6e-4 * point.rank,
        "pallas_fused": 0.09 + 7e-5 * k + 2e-5 * point.tile_rows,
        "pallas_fused_tiled": 0.095 + 7e-5 * k + 2e-5 * point.tile_rows,
        "pallas_fused_bf16": 0.04 + 4e-5 * k + 2e-5 * point.tile_rows,
        "pallas_fused_gather": 0.07 + 5e-5 * k + 2e-5 * point.tile_rows,
        "pallas_fused_gather_tiled":
            0.075 + 5e-5 * k + 2e-5 * point.tile_rows,
        "pallas_fused_gather_bf16":
            0.03 + 3e-5 * k + 2e-5 * point.tile_rows,
        # Streaming re-fetches window tiles per block: slower than the
        # resident gathers, still ahead of the row-gathering fused path
        # on traffic — mirroring the counted per-nonzero bytes.
        "pallas_fused_gather_stream":
            0.08 + 6e-5 * k + 2e-5 * point.tile_rows + 1e-5 * point.blk,
    }[backend]


def _segsum_step(idx, val, valid, factors, rows_cap: int):
    """The plain-XLA backend ``core.distributed.device_mttkrp`` uses."""
    local_row = jnp.where(valid, idx[:, 0], 0)
    ell = jnp.where(valid, val, 0.0)[:, None].astype(factors[0].dtype)
    for w in range(1, idx.shape[1]):
        ell = ell * jnp.take(factors[w], idx[:, w], axis=0)
    return jax.ops.segment_sum(
        ell.astype(jnp.float32), local_row, num_segments=rows_cap,
        indices_are_sorted=True,
    )


def _time(fn: Callable, *, warmup: int, iters: int) -> float:
    # Shared steady-state idiom (repro.obs.prof.harness): fenced warmup
    # + repeats, robust median with outlier rejection at iters >= 4 —
    # calibration runs long enough to catch a GC pause now reject it
    # instead of baking it into the table's argmins.
    from ..obs.prof import harness as _harness

    return _harness.measure_steady(fn, warmup=warmup, repeats=iters).median_s


def _real_measure(*, seed: int, warmup: int, iters: int) -> Callable:
    """Default ``measure(backend, point)``: actually run the kernels."""
    cases: dict = {}

    def measure(backend: str, point: GridPoint) -> float:
        if point not in cases:
            cases[point] = make_case(point, seed=seed)
        idx, val, valid, factors, rows_cap = cases[point]
        if backend == "segsum":
            step = jax.jit(_segsum_step, static_argnames=("rows_cap",))
            fn = lambda: step(idx, val, valid, factors, rows_cap=rows_cap)
        else:
            # Execution mode comes from the repro.runtime.execution
            # policy: interpret on CPU hosts, compiled on TPU — the same
            # resolution the production dispatch uses, so a table
            # calibrated on hardware times real Mosaic kernels.
            fn = lambda: kops.mttkrp_device_step(
                idx, val, valid, factors, mode=0, rows_cap=rows_cap,
                row_offset=0, blk=point.blk, tile_rows=point.tile_rows,
                backend=backend,
            )
        return _time(fn, warmup=warmup, iters=iters)

    return measure


def calibrate(
    grid: Iterable[GridPoint] | None = None,
    *,
    quick: bool = True,
    backends: Sequence[str] = BACKENDS,
    measure: Callable | None = None,
    seed: int = 0,
    warmup: int = 1,
    iters: int = 2,
    meta_extra: dict | None = None,
    verbose: bool = False,
) -> CalibrationTable:
    """Measure ``backends`` over ``grid`` and return a CalibrationTable.

    ``measure(backend, point) -> seconds`` defaults to real wall-clock
    measurement on this host; tests pass a deterministic stub.
    """
    points = list(grid) if grid is not None else default_grid(quick=quick)
    if measure is None:
        measure = _real_measure(seed=seed, warmup=warmup, iters=iters)
    tracer = _tracer_mod.get_tracer()
    entries = []
    measured_s: dict[str, float] = {}
    with tracer.span("calibrate", points=len(points),
                     backends=len(backends)):
        for point in points:
            timings = {}
            with tracer.span("point", nmodes=point.nmodes, rank=point.rank,
                             blk=point.blk, tile_rows=point.tile_rows,
                             density=point.density):
                for b in backends:
                    with tracer.span("measure", backend=b):
                        timings[b] = float(measure(b, point))
                    _obs.add("tune.measure_s", timings[b], backend=b)
                    measured_s[b] = measured_s.get(b, 0.0) + timings[b]
                _obs.add("tune.points")
            entries.append(CalibrationEntry(
                nmodes=point.nmodes, rank=point.rank, blk=point.blk,
                tile_rows=point.tile_rows, density=point.density,
                timings_s=timings, factor_rows=case_factor_rows(point),
                stream_window_tiles=case_stream_window_tiles(point),
            ))
            if verbose:
                best = entries[-1].best
                print(f"  {point}: best={best} "
                      + " ".join(f"{b}={t:.4f}s"
                                 for b, t in timings.items()),
                      flush=True)
    # The table carries its own observability summary: how much wall
    # time the calibration spent per backend and how many spans the
    # tracer recorded. A committed table thereby documents its
    # measurement cost, not just its argmins.
    obs_meta = {
        "points": len(points),
        "measure_s": {b: round(s, 6) for b, s in sorted(measured_s.items())},
        "spans": len(tracer.records) if tracer.enabled else 0,
    }
    meta = host_meta(dict(meta_extra or {}, quick=quick, seed=seed,
                          obs=obs_meta))
    return CalibrationTable(entries=entries, meta=meta)
