"""Cost model: interpolate a calibration table to unseen configurations.

The table holds measurements on a discrete grid; the dispatch needs an
answer for *any* ``(nmodes, rank, blk, tile_rows)``. The model:

  * groups entries by ``(nmodes, blk, tile_rows, density)``;
  * within a group, interpolates backend time piecewise-linearly in
    ``log2(rank)`` (spMTTKRP traffic — and therefore time — is linear in
    R, so log-spaced rank knots interpolate well), clamped at the ends;
  * off-grid ``(nmodes, blk, tile_rows)`` resolve to the nearest
    measured group: exact ``nmodes`` preferred, then smallest log-ratio
    distance on ``(blk, tile_rows)``;
  * ``density=None`` aggregates over the measured densities (median),
    so an in-grid query reproduces the measured argmin exactly.

Every query can return ``None`` (table can't answer — e.g. no entries,
or a backend never measured); callers then fall back to the static VMEM
model, bit-identical to the untuned dispatch.

:func:`plan_modes` turns a table into per-mode tuned
``(backend, blk, tile_rows)`` plans for ``DynasorRuntime``.
"""
from __future__ import annotations

import math
from typing import Sequence

import numpy as np

from ..core.distributed import ModePlan
from ..kernels.mttkrp.ops import (AUTO_BACKENDS, MIN_MXU_RANK,
                                  MXU_RANK_MULTIPLE, padded_rank,
                                  select_backend)
from ..oocore import planner as _planner

__all__ = ["CostModel", "compare_dispatch", "plan_modes"]


def _feasible(backends, nmodes: int, rank: int, blk: int, tile_rows: int,
              *, covered: bool, factor_rows=None):
    """Filter ``backends`` by the same hard constraints select_backend's
    table path applies: per-family VMEM feasibility via the one
    ``repro.oocore`` residency authority
    (:func:`repro.oocore.planner.backend_fits` — the gather family,
    streaming included, needs ``factor_rows`` to certify; ``None`` rules
    it out), and no MXU one-hot backend below ``MIN_MXU_RANK`` unless
    that rank was actually measured (``covered`` — below-grid
    extrapolation is not evidence)."""
    out = []
    for b in backends:
        if rank < MIN_MXU_RANK and not covered and b.startswith("pallas"):
            continue
        if not _planner.backend_fits(b, nmodes=nmodes, rank=rank, blk=blk,
                                     tile_rows=tile_rows,
                                     factor_rows=factor_rows):
            continue
        out.append(b)
    return out


class CostModel:
    """Interpolating view over a :class:`repro.tune.table.CalibrationTable`."""

    def __init__(self, table):
        entries = getattr(table, "entries", table)
        # (nmodes, blk, tile_rows) -> density -> backend -> {rank: seconds}
        groups: dict = {}
        backends: set[str] = set()
        for e in entries:
            key = (e.nmodes, e.blk, e.tile_rows)
            by_density = groups.setdefault(key, {})
            by_backend = by_density.setdefault(float(e.density), {})
            for b, t in e.timings_s.items():
                by_backend.setdefault(b, {})[e.rank] = float(t)
                backends.add(b)
        # Freeze each {rank: t} map into sorted knot arrays for np.interp.
        self._groups = {
            key: {
                d: {
                    b: (np.array(sorted(rt)),
                        np.array([rt[r] for r in sorted(rt)]))
                    for b, rt in bb.items()
                }
                for d, bb in by_density.items()
            }
            for key, by_density in groups.items()
        }
        self.backends = tuple(sorted(backends))

    # -- group / density resolution ---------------------------------------

    def _nearest_group(self, nmodes: int, blk: int, tile_rows: int):
        if not self._groups:
            return None
        exact = (nmodes, blk, tile_rows)
        if exact in self._groups:
            return self._groups[exact]

        def dist(key):
            n, b, t = key
            shape_d = (abs(math.log2(b / blk))
                       + abs(math.log2(t / tile_rows)))
            return (abs(n - nmodes), shape_d, key)

        return self._groups[min(self._groups, key=dist)]

    @staticmethod
    def _nearest_density(by_density: dict, density: float):
        return by_density[min(
            by_density,
            key=lambda d: (abs(math.log(max(d, 1e-9) / max(density, 1e-9))),
                           d),
        )]

    # -- queries -----------------------------------------------------------

    def predict(self, backend: str, *, nmodes: int, rank: int, blk: int,
                tile_rows: int, density: float | None = None) -> float | None:
        """Interpolated seconds for one backend, or ``None`` if unanswerable."""
        group = self._nearest_group(nmodes, blk, tile_rows)
        if group is None:
            return None
        if density is None:
            curves = [bb[backend] for bb in group.values() if backend in bb]
        else:
            bb = self._nearest_density(group, density)
            curves = [bb[backend]] if backend in bb else []
        if not curves:
            return None
        lr = math.log2(max(rank, 1))
        vals = [float(np.interp(lr, np.log2(ranks), times))
                for ranks, times in curves]
        return float(np.median(vals))

    def covers(self, *, nmodes: int, rank: int, blk: int,
               tile_rows: int) -> bool:
        """Is ``rank`` within the measured knot span of the resolved group?

        Queries outside the span are clamped extrapolations — fine for
        large ranks (the VMEM guard protects the only hazard there) but
        not a license to override the static rank<8 MXU-padding rule
        with timings never measured at tiny ranks; the dispatch checks
        this before letting a table answer below that threshold.
        """
        group = self._nearest_group(nmodes, blk, tile_rows)
        if group is None:
            return False
        knots = [r for bb in group.values()
                 for ranks, _ in bb.values() for r in ranks]
        return bool(knots) and min(knots) <= rank <= max(knots)

    def best_backend(self, *, nmodes: int, rank: int, blk: int,
                     tile_rows: int, allowed: Sequence[str] | None = None,
                     density: float | None = None) -> str | None:
        """Argmin backend over ``allowed`` (ties break by name), or ``None``."""
        candidates = self.backends if allowed is None else tuple(allowed)
        scored = []
        for b in sorted(set(candidates)):
            t = self.predict(b, nmodes=nmodes, rank=rank, blk=blk,
                             tile_rows=tile_rows, density=density)
            if t is not None:
                scored.append((t, b))
        if not scored:
            return None
        return min(scored)[1]

    def shape_candidates(self, nmodes: int) -> list[tuple[int, int]]:
        """Measured ``(blk, tile_rows)`` pairs, preferring exact ``nmodes``."""
        exact = sorted({(b, t) for (n, b, t) in self._groups if n == nmodes})
        if exact:
            return exact
        return sorted({(b, t) for (_, b, t) in self._groups})


def compare_dispatch(table, key) -> dict:
    """Static vs. calibrated vs. oracle decision at one dispatch key.

    The one shared definition of the consistency standard, used by both
    ``repro.tune check`` and ``benchmarks.bench_dispatch`` so they can
    never disagree. ``oracle`` is the measured argmin over the backends
    ``auto`` may actually pick (the numerics-preserving
    ``AUTO_BACKENDS`` — a measured-fast bf16 is not a valid target for a
    dispatch that must not change results); when the table timed none of
    them, the static rule *is* the standard (the table cannot answer).
    """
    from .table import (AUTO_BACKENDS, aggregate_timings, key_factor_rows,
                        measured_best)

    nmodes, rank, blk, tile_rows = key
    agg = aggregate_timings(table, key)
    # The measured case's factor sizes (v3 entries) — without them the
    # dispatch can't certify gather feasibility, so static/calibrated
    # both stay off the gather family, exactly like a live dispatch
    # whose caller doesn't know the factor shapes.
    factor_rows = key_factor_rows(table, key)
    kw = dict(nmodes=nmodes, rank=rank, blk=blk, tile_rows=tile_rows,
              factor_rows=factor_rows)
    static = select_backend("auto", **kw)
    calibrated = select_backend("auto", table=table, **kw)
    oracle = measured_best(agg, allowed=AUTO_BACKENDS)
    if oracle is None:
        oracle = static
    return dict(agg=agg, static=static, calibrated=calibrated,
                oracle=oracle, factor_rows=factor_rows)


def plan_modes(table, ft, rank: int, *,
               allowed: Sequence[str] | None = None,
               num_workers: int | None = None,
               ordering: str = "none") -> tuple[ModePlan, ...] | None:
    """Tuned per-mode ``(backend, blk, tile_rows)`` plans for a tensor.

    For every output mode the model scores each measured ``(blk,
    tile_rows)`` shape × backend at that mode's own nonzero density
    (per-worker nonzeros per ``blk × row-tile`` block — skewed modes
    have emptier blocks) and keeps the global argmin. Returns ``None``
    when the table cannot answer (empty / no overlapping backends), so
    callers keep the static configuration.

    With ``allowed=None`` the candidate pool is every measured backend
    in ``AUTO_BACKENDS`` (plus ``segsum``): like ``select_backend``'s
    table path, an automatic planner must not change numerics on timing
    evidence, nor follow a backend this module can no longer run.
    Pass ``allowed`` explicitly (e.g. ``table.model.backends``) to let a
    bf16-opted-in runtime plan with them.

    ``ordering`` (:data:`repro.reorder.ORDERINGS`) is carried verbatim
    into every plan — the locality policy is a numerics-order choice
    the caller owns, not something the cost model selects.
    """
    model = table if isinstance(table, CostModel) else CostModel(table)
    D = num_workers if num_workers is not None else ft.params.num_workers
    nnz_per_worker = max(1.0, ft.nnz / max(D, 1))
    plans = []
    for n in range(ft.nmodes):
        rows_per_worker = max(1, ft.modes[n].rows_cap)
        # Replicated input-factor rows this mode's gather kernel would
        # hold resident (per-mode i_pad over non-output modes; the final
        # tile-rounding of rows_cap adds at most D·tile_rows per mode —
        # noise against the VMEM budget). The per-mode tuple lets the
        # residency planner size exact stream windows.
        factor_rows = tuple(D * ft.modes[w].rows_cap
                            for w in range(ft.nmodes) if w != n)
        best = None
        for blk, tile_rows in model.shape_candidates(ft.nmodes):
            num_tiles = max(1, -(-rows_per_worker // tile_rows))
            density = nnz_per_worker / (num_tiles * blk)
            # Default pool = measured ∩ (AUTO_BACKENDS + segsum): the one
            # numerics-preserving policy defined in ops.py, plus the
            # distributed layer's own segsum path.
            cand_allowed = (
                [b for b in model.backends
                 if b == "segsum" or b in AUTO_BACKENDS]
                if allowed is None else allowed)
            cand_allowed = _feasible(
                cand_allowed, ft.nmodes, rank, blk, tile_rows,
                covered=model.covers(nmodes=ft.nmodes, rank=rank, blk=blk,
                                     tile_rows=tile_rows),
                factor_rows=factor_rows)
            choice = model.best_backend(
                nmodes=ft.nmodes, rank=rank, blk=blk, tile_rows=tile_rows,
                allowed=cand_allowed, density=density)
            if choice is None:
                continue
            t = model.predict(choice, nmodes=ft.nmodes, rank=rank, blk=blk,
                              tile_rows=tile_rows, density=density)
            cand = (t, blk, tile_rows, choice)
            if best is None or cand < best:
                best = cand
        if best is None:
            return None
        _, blk, tile_rows, backend = best
        slabs = (padded_rank(rank) // MXU_RANK_MULTIPLE
                 if backend in ("pallas_fused_tiled",
                                "pallas_fused_gather_tiled",
                                _planner.STREAM_BACKEND) else 1)
        window = (tuple(_planner.stream_window_tiles(blk, r)
                        for r in factor_rows)
                  if backend == _planner.STREAM_BACKEND else ())
        plans.append(ModePlan(backend=backend, blk=blk, tile_rows=tile_rows,
                              rank_slabs=slabs, window_tiles=window,
                              ordering=ordering))
    return tuple(plans)
