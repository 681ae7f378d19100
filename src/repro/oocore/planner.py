"""Unified factor-residency planner for the MTTKRP backend dispatch.

Before this module, the "does it fit?" question was answered in three
places with three ad-hoc rule sets: ``ops.select_backend``'s static
ladder, the per-family guards in ``ops``'s table-validation path, and
``tune.model._feasible``'s copy of both. Every one of them was really
asking the same thing: **under a byte budget, what residency can the
per-mode factor operands afford?** This module owns that question.

:func:`plan_residency` returns a :class:`ResidencyPlan` — the full
decision for one mode step: the chosen backend, the per-input-factor
residency policy (``whole`` / ``slab`` / ``stream``), the VMEM bytes the
choice costs, and the stream-window geometry when the out-of-core
kernel is chosen. The policies map 1:1 onto the kernel families:

  ``whole``   the factor matrix is VMEM-resident across the grid sweep
              (``fused_mttkrp_nmode_gather``);
  ``slab``    one ``RANK_SLAB``-wide column slab of the factor is
              resident per slab pass (``fused_mttkrp_nmode_gather_tiled``);
  ``stream``  the factor stays **HBM-resident** and ``window_tiles``
              slots of ``FACTOR_ROW_TILE`` rows are DMA'd through VMEM
              per nonzero block (``fused_mttkrp_nmode_gather_stream``) —
              the out-of-core regime this package adds.

When even streaming cannot be certified (factor sizes unknown, or the
window itself overflows), the plan degrades through the row-gathering
rungs, fused → rank-tiled fused, and to the segment-sum ``ref`` where
not even one rank slab fits.

The ladder is *monotone in the budget* by construction: every
feasibility predicate is ``bytes ≤ budget``, so growing the budget can
only move the decision toward earlier (more-resident) rungs — a
property ``tests/test_oocore.py`` sweeps.

Consumers: ``kernels.mttkrp.ops.select_backend`` (static decision +
calibration-table validation), ``tune.model.plan_modes`` (per-mode tuned
planning), ``oocore.executor`` (window geometry + chunk budgeting).
This module imports only ``kernels.mttkrp.kernel`` (the byte formulas),
never ``ops`` — ops imports *us*.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

from ..kernels.mttkrp import kernel as _kernel
from ..obs import counters as _obs

__all__ = [
    "FACTOR_ROW_TILE",
    "MIN_MXU_RANK",
    "VMEM_BUDGET_BYTES",
    "STREAM_BACKEND",
    "SMEM_BUDGET_BYTES",
    "FactorResidency",
    "ResidencyPlan",
    "StreamTraffic",
    "backend_fits",
    "block_tile_analysis",
    "chunk_boundaries",
    "chunk_window_tiles",
    "padded_rank",
    "plan_residency",
    "predict_stream_traffic",
    "stream_chunk_bytes",
    "stream_smem_bytes",
    "stream_window_tiles",
]

FACTOR_ROW_TILE = _kernel.FACTOR_ROW_TILE

# Below this rank the one-hot MXU matmul pads R to MXU_RANK_MULTIPLE and
# wastes >= 16x of the array; the XLA segment-sum reference wins.
# (kernel.py owns these shared constants — it is the only module in the
# dispatch triangle with no intra-repo imports, so ops.py and this
# planner can alias one definition whichever is imported first.)
MIN_MXU_RANK = _kernel.MIN_MXU_RANK

# Per-core VMEM working-set budget (half of a v5e core's ~128 MiB VMEM —
# the same theta=0.5 cache-fraction stance as the paper's Eq. 3).
VMEM_BUDGET_BYTES = _kernel.VMEM_BUDGET_BYTES

# The out-of-core backend this package adds to ops.BACKENDS.
STREAM_BACKEND = _kernel.STREAM_BACKEND_NAME

SMEM_BUDGET_BYTES = _kernel.SMEM_BUDGET_BYTES


# R rounded up to the MXU lane multiple — aliased from kernel.py, the
# single source shared with ops.py's dispatch arithmetic.
padded_rank = _kernel.padded_rank

# Operand blocks a row-gathering rung (pallas_fused, pallas_fused_tiled)
# gives its kernel per grid step: the one rank-major
# Khatri-Rao block ``krp_scatter`` scatters, whatever the tensor order.
KRP_OPERANDS = 1


def factor_row_tiles(rows: int, frow_tile: int = FACTOR_ROW_TILE) -> int:
    """Number of ``frow_tile``-row tiles covering a ``rows``-row factor."""
    return max(1, -(-rows // frow_tile))


def stream_window_tiles(blk: int, rows: int,
                        frow_tile: int = FACTOR_ROW_TILE) -> int:
    """Correctness bound on the stream kernel's per-mode window width.

    A block of ``blk`` nonzeros touches at most ``blk`` distinct factor
    row tiles, and never more tiles than the factor has — so a window of
    ``min(blk, ceil(rows / frow_tile))`` slots always holds every tile a
    block needs, for any index distribution. The executor may shrink
    this with measured per-block distinct-tile counts; the static
    dispatch (which cannot look at data) plans with the bound.
    """
    return min(blk, factor_row_tiles(rows, frow_tile))


def stream_smem_bytes(num_blocks: int, window_tiles: Sequence[int]) -> int:
    """SMEM the stream kernel's scalar-prefetched operands occupy.

    ``tile_of_block`` (``num_blocks`` int32, 1024-word tiles) plus one
    ``(num_blocks, W_w)`` int32 schedule per input mode, which SMEM pads
    to (8, 128)-word tiles. All of it is prefetched whole, so it grows
    with the nonzero count — unlike the VMEM window.
    """
    def up(x, m):
        return -(-x // m) * m
    scheds = sum(up(num_blocks, 8) * up(int(w), 128) for w in window_tiles)
    return 4 * (up(num_blocks, 1024) + scheds)


@dataclasses.dataclass(frozen=True)
class FactorResidency:
    """Residency of one input-factor matrix under a :class:`ResidencyPlan`."""

    rows: int                   # factor rows (I_pad of the input mode)
    policy: str                 # "whole" | "slab" | "stream"
    window_tiles: int           # FACTOR_ROW_TILE-row tiles resident per pass
    rank_cols: int              # rank columns resident per pass
    resident_bytes: int         # VMEM bytes this factor holds per grid step

    @property
    def row_tiles(self) -> int:
        """Total row tiles of this factor (streamed tiles partition them)."""
        return factor_row_tiles(self.rows)

    def tile_spans(self) -> list[tuple[int, int]]:
        """Disjoint ``[start, stop)`` row ranges, one per row tile.

        The streaming schedule fetches whole tiles; these spans are the
        units it fetches. They must partition ``[0, rows)`` exactly —
        every factor row covered exactly once — which
        ``tests/test_oocore.py`` asserts as a plan invariant.
        """
        return [(t * FACTOR_ROW_TILE, min(self.rows, (t + 1) * FACTOR_ROW_TILE))
                for t in range(self.row_tiles)]


@dataclasses.dataclass(frozen=True)
class ResidencyPlan:
    """One mode step's residency decision under a byte budget."""

    backend: str                        # the certified kernels.mttkrp backend
    nmodes: int
    rank: int
    blk: int
    tile_rows: int
    vmem_budget: int
    gather_itemsize: int
    vmem_bytes: int                     # working set of the chosen backend
    rank_slabs: int                     # slab passes the choice implies
    window_tiles: tuple[int, ...]       # per input mode; () unless streaming
    factors: tuple[FactorResidency, ...]  # () when factor sizes are unknown

    @property
    def streams(self) -> bool:
        return self.backend == STREAM_BACKEND

    @property
    def fits(self) -> bool:
        """Did the chosen backend's working set fit the budget?

        Every rung the ladder returns does: the last resort is ``ref``,
        which holds nothing in VMEM.
        """
        return self.vmem_bytes <= self.vmem_budget


def _normalize_factor_rows(factor_rows, num_in_modes: int):
    """``factor_rows`` as (per-mode tuple | None, total | None).

    Callers know the factor sizes at three fidelities: not at all
    (``None`` — a purely shape-keyed dispatch query), as the total row
    count ``Σ I_pad`` (the historical ``select_backend`` int), or
    per input mode (``mttkrp_device_step``, the executor). Aggregate
    ints plan the stream window conservatively, as if every input
    factor had all the rows.
    """
    if factor_rows is None:
        return None, None
    if isinstance(factor_rows, (list, tuple)):
        per_mode = tuple(int(r) for r in factor_rows)
        assert len(per_mode) == num_in_modes, (per_mode, num_in_modes)
        return per_mode, sum(per_mode)
    total = int(factor_rows)
    return None, total


def backend_fits(backend: str, *, nmodes: int, rank: int, blk: int,
                 tile_rows: int, factor_rows=None,
                 vmem_budget: int = VMEM_BUDGET_BYTES,
                 gather_itemsize: int = 4,
                 window_tiles: Sequence[int] | None = None,
                 num_blocks: int | None = None) -> bool:
    """Hard VMEM feasibility of one backend — the single predicate.

    This is what bounds a calibration table's preference in
    ``select_backend`` and filters ``plan_modes``' candidate pool: a
    measured-fast backend whose working set cannot be certified under
    the budget is an extrapolation and must be discarded. Non-Pallas
    backends (``ref``, ``segsum``) always "fit" — they hold nothing in
    VMEM. The ``*_bf16`` names fold into ``gather_itemsize=2``.

    ``window_tiles`` (streaming rung only) overrides the static
    worst-case per-input-mode window widths with measured/predicted
    ones — :func:`predict_stream_traffic` on a locality-reordered
    stream (``repro.reorder``) typically certifies the rung at budgets
    the data-blind bound cannot.

    ``num_blocks`` (the aligned stream's block count) lets the streaming
    rung also be checked against :data:`SMEM_BUDGET_BYTES`: its tile
    schedules are scalar-prefetched whole (:func:`stream_smem_bytes`).
    ``None`` (a shape-only query) leaves SMEM unchecked.
    """
    if backend.endswith("_bf16"):
        backend = backend[:-len("_bf16")]
        gather_itemsize = 2
    k, rpad = nmodes - 1, padded_rank(rank)
    # The row-gathering rungs hand krp_scatter one fp32 Khatri-Rao operand
    # block whatever N and the gather dtype are (KRP_OPERANDS).
    if backend == "pallas_fused":
        return _kernel.fused_vmem_bytes(
            KRP_OPERANDS, rpad, blk, tile_rows) <= vmem_budget
    if backend == "pallas_fused_tiled":
        return _kernel.fused_tiled_vmem_bytes(
            KRP_OPERANDS, rpad, blk, tile_rows) <= vmem_budget
    per_mode, total = _normalize_factor_rows(factor_rows, k)
    if backend == "pallas_fused_gather":
        return total is not None and _kernel.gather_vmem_bytes(
            k, rpad, blk, tile_rows, total,
            gather_itemsize=gather_itemsize) <= vmem_budget
    if backend == "pallas_fused_gather_tiled":
        return total is not None and _kernel.gather_tiled_vmem_bytes(
            k, rpad, blk, tile_rows, total,
            gather_itemsize=gather_itemsize) <= vmem_budget
    if backend == STREAM_BACKEND:
        if total is None:
            return False
        if window_tiles is not None:
            windows = tuple(int(w) for w in window_tiles)
            assert len(windows) == k, (windows, k)
        elif per_mode is not None:
            windows = tuple(stream_window_tiles(blk, r) for r in per_mode)
        else:
            windows = (stream_window_tiles(blk, total),) * k
        if num_blocks is not None and stream_smem_bytes(
                num_blocks, windows) > SMEM_BUDGET_BYTES:
            return False
        return _kernel.gather_stream_vmem_bytes(
            k, rpad, blk, tile_rows, windows,
            gather_itemsize=gather_itemsize) <= vmem_budget
    # ref / segsum (and anything dispatched a layer up).
    return True


def _factor_states(per_mode, total, k: int, policy: str, blk: int,
                   rank_cols: int, gi: int,
                   windows=None) -> tuple[FactorResidency, ...]:
    rows_list = per_mode if per_mode is not None else (total,) * k
    states = []
    for i, rows in enumerate(rows_list):
        if policy == "stream":
            w = (int(windows[i]) if windows is not None
                 else stream_window_tiles(blk, rows))
            # A window covering every tile of the factor is de-facto
            # whole residency — the plan records it honestly.
            pol = "whole" if w >= factor_row_tiles(rows) else "stream"
            resident = w * FACTOR_ROW_TILE * rank_cols * gi
        else:
            pol, w = policy, factor_row_tiles(rows)
            resident = rows * rank_cols * gi
        states.append(FactorResidency(
            rows=rows, policy=pol, window_tiles=w, rank_cols=rank_cols,
            resident_bytes=resident))
    return tuple(states)


def plan_residency(*, nmodes: int, rank: int, blk: int = 512,
                   tile_rows: int = 128, factor_rows=None,
                   vmem_budget: int = VMEM_BUDGET_BYTES,
                   gather_itemsize: int = 4,
                   allow_stream: bool = True,
                   window_tiles: Sequence[int] | None = None,
                   num_blocks: int | None = None
                   ) -> ResidencyPlan:
    """The full static residency ladder for one mode step.

    In order (each rung = one feasibility predicate against
    ``vmem_budget``; the first that holds wins, so the decision is
    monotone in the budget):

      1. ``rank < MIN_MXU_RANK`` → ``ref`` (MXU-padding waste);
      2. factors whole-VMEM        → ``pallas_fused_gather``;
      3. one rank slab resident    → ``pallas_fused_gather_tiled``;
      4. bounded tile window fits  → ``pallas_fused_gather_stream``
         (the out-of-core rung — factors stay in HBM);
      5. fused working set fits    → ``pallas_fused``;
      6. one fused rank slab fits  → ``pallas_fused_tiled``;
      7. otherwise                 → ``ref`` (no Pallas rung fits).

    Rungs 2–4 need ``factor_rows`` (an int total, or a per-input-mode
    sequence for exact stream windows); without it they are skipped and
    the decision is bit-identical to the pre-gather dispatch.
    ``allow_stream=False`` removes rung 4 (the pre-oocore ladder).

    ``window_tiles`` overrides rung 4's static worst-case window widths
    with measured/predicted per-input-mode ones (see
    :func:`predict_stream_traffic`): after a ``repro.reorder`` locality
    sort the per-block distinct-tile maxima shrink well below the
    data-blind ``min(blk, ceil(rows/128))`` bound, and this is how the
    stream rung gets certified — and picked — at budgets where the
    static bound overflows. The ladder stays monotone in the budget:
    the override only changes rung 4's (fixed) byte cost, never the
    predicate shape. ``num_blocks`` adds rung 4's SMEM check (see
    :func:`backend_fits`).
    """
    k, rpad = nmodes - 1, padded_rank(rank)
    gi = gather_itemsize
    kw = dict(nmodes=nmodes, rank=rank, blk=blk, tile_rows=tile_rows,
              vmem_budget=vmem_budget, gather_itemsize=gi)
    per_mode, total = _normalize_factor_rows(factor_rows, k)

    def finish(backend, vmem_bytes, rank_slabs=1, window=(), factors=()):
        # Static arithmetic (runs at jit-trace time) → counted once per
        # unique plan query per process: eligible for the obs baseline.
        _obs.add("planner.plans")
        _obs.add("planner.vmem.plan_bytes", int(vmem_bytes),
                 backend=backend)
        return ResidencyPlan(
            backend=backend, vmem_bytes=int(vmem_bytes),
            rank_slabs=rank_slabs, window_tiles=tuple(window),
            factors=tuple(factors), **kw)

    if rank < MIN_MXU_RANK:
        return finish("ref", 0)
    slabs = rpad // _kernel.RANK_SLAB
    if total is not None:
        if backend_fits("pallas_fused_gather", factor_rows=factor_rows,
                        **kw):
            return finish(
                "pallas_fused_gather",
                _kernel.gather_vmem_bytes(k, rpad, blk, tile_rows, total,
                                          gather_itemsize=gi),
                factors=_factor_states(per_mode, total, k, "whole", blk,
                                       rpad, gi))
        if backend_fits("pallas_fused_gather_tiled",
                        factor_rows=factor_rows, **kw):
            return finish(
                "pallas_fused_gather_tiled",
                _kernel.gather_tiled_vmem_bytes(
                    k, rpad, blk, tile_rows, total, gather_itemsize=gi),
                rank_slabs=slabs,
                factors=_factor_states(per_mode, total, k, "slab", blk,
                                       min(rpad, _kernel.RANK_SLAB), gi))
        if allow_stream and backend_fits(STREAM_BACKEND,
                                         factor_rows=factor_rows,
                                         window_tiles=window_tiles,
                                         num_blocks=num_blocks, **kw):
            if window_tiles is not None:
                windows = tuple(int(w) for w in window_tiles)
            elif per_mode is not None:
                windows = tuple(stream_window_tiles(blk, r) for r in per_mode)
            else:
                windows = (stream_window_tiles(blk, total),) * k
            return finish(
                STREAM_BACKEND,
                _kernel.gather_stream_vmem_bytes(
                    k, rpad, blk, tile_rows, windows, gather_itemsize=gi),
                rank_slabs=slabs, window=windows,
                factors=_factor_states(per_mode, total, k, "stream", blk,
                                       min(rpad, _kernel.RANK_SLAB), gi,
                                       windows=windows))
    if backend_fits("pallas_fused", **kw):
        return finish("pallas_fused",
                      _kernel.fused_vmem_bytes(KRP_OPERANDS, rpad, blk,
                                               tile_rows))
    if backend_fits("pallas_fused_tiled", **kw):
        return finish("pallas_fused_tiled",
                      _kernel.fused_tiled_vmem_bytes(
                          KRP_OPERANDS, rpad, blk, tile_rows),
                      rank_slabs=slabs)
    return finish("ref", 0)


# ---------------------------------------------------------------------------
# Chunk planning (shared by the executor and the traffic predictor)
# ---------------------------------------------------------------------------

def chunk_boundaries(tile_of_block, max_blocks: int) -> list[tuple[int, int]]:
    """Split ``num_blocks`` blocks into chunks of at most ``max_blocks``.

    Boundaries prefer output-row-tile edges: a chunk ends at the last
    position ``<= max_blocks`` where ``tile_of_block`` changes, so a
    tile's contiguous run of blocks stays in one chunk whenever it fits.
    A run longer than ``max_blocks`` is split mid-tile (the executor's
    ``out_init`` threading keeps that exact). Returns ``[start, stop)``
    block ranges covering every block exactly once.
    """
    tiles = np.asarray(tile_of_block)
    num_blocks = len(tiles)
    assert max_blocks >= 1, max_blocks
    bounds = []
    start = 0
    while start < num_blocks:
        stop = min(start + max_blocks, num_blocks)
        if stop < num_blocks:
            aligned = stop
            while aligned > start + 1 and tiles[aligned] == tiles[aligned - 1]:
                aligned -= 1
            if aligned > start and tiles[aligned] != tiles[aligned - 1]:
                stop = aligned
        bounds.append((start, stop))
        start = stop
    return bounds


def chunk_window_tiles(distinct_counts, chunks, windows):
    """Per-chunk stream-window widths, tightened chunk by chunk.

    Every chunk is its own kernel call with its own *static* schedule
    width, so the width only has to cover that chunk's blocks — not the
    global worst block. ``distinct_counts`` is the ``(num_blocks, K)``
    per-block distinct-tile matrix from :func:`block_tile_analysis`,
    ``chunks`` the ``[start, stop)`` list from :func:`chunk_boundaries`,
    ``windows`` the global (VMEM-certified) per-mode widths that cap
    each chunk's. Returns one ``K``-tuple per chunk.

    This is the mechanism a ``repro.reorder`` locality sort cashes in
    on: post-sort, tile diversity concentrates into few blocks, so
    almost every chunk's width collapses to 1–2 while only the chunk
    holding the rare-tile tail pays the wide window. On an unsorted
    stream the per-block counts are i.i.d.-ish and every chunk's max is
    near the global max — tightening buys little.
    """
    distinct_counts = np.asarray(distinct_counts)
    k = distinct_counts.shape[1]
    assert len(windows) == k, (windows, k)
    return [
        tuple(int(min(windows[i],
                      max(1, int(distinct_counts[start:stop, i].max()))))
              for i in range(k))
        for start, stop in chunks
    ]


def stream_chunk_bytes(blk: int, k: int, windows) -> int:
    """Aligned-operand bytes one block contributes to a chunk budget.

    Values (f32) + local rows (i32) + ``K`` index streams (i32) per
    slot, plus one ``i32`` schedule row entry per window slot — the
    arrays the executor slices per chunk.
    """
    return blk * (4 + 4 + 4 * k) + 4 * sum(windows)


# ---------------------------------------------------------------------------
# Data-dependent stream-traffic prediction (the repro.reorder cost model)
# ---------------------------------------------------------------------------

def block_tile_analysis(per_block_tiles: np.ndarray):
    """Per-block sorted-distinct analysis of an aligned tile stream.

    ``per_block_tiles`` is ``(num_blocks, blk, K)`` int — the
    ``FACTOR_ROW_TILE``-tile id of every aligned stream slot, per
    gathered mode. Returns ``(sorted_tiles, first, rank_of,
    distinct_counts)``: the per-block sorted tiles, the first-occurrence
    mask, each slot's distinct rank, and the ``(num_blocks, K)``
    distinct-tile counts. This is the **one** analysis behind the
    executor's window tightening + tile schedules + counted
    ``StreamStats`` *and* :func:`predict_stream_traffic` — sharing it is
    what makes the planner's prediction and the executor's count agree
    exactly (``tests/test_reorder.py`` pins it).
    """
    st = np.sort(per_block_tiles, axis=1)
    first = np.concatenate(
        [np.ones((st.shape[0], 1, st.shape[2]), bool),
         st[:, 1:] != st[:, :-1]], axis=1)
    rank_of = np.cumsum(first, axis=1) - 1
    distinct_counts = first.sum(axis=1)
    return st, first, rank_of, distinct_counts


@dataclasses.dataclass(frozen=True)
class StreamTraffic:
    """Predicted tile-fetch traffic of one streamed mode step.

    Counted from the data (per-block distinct-tile analysis of the
    block-aligned stream), so it matches the executor's ``StreamStats``
    exactly — the point being that :func:`plan_residency` can consume
    ``window_tiles`` *before* running anything, and pick the stream
    rung when a ``repro.reorder`` pass makes it win.
    """

    ordering: str                   # stream the prediction was made on
    num_blocks: int
    nnz: int
    window_tiles: tuple[int, ...]   # global tightened widths, per input mode
    scheduled_tiles: int            # Σ_chunks blocks_c * Σ chunk windows
    distinct_tiles: int             # Σ per-block distinct, all modes
    tile_bytes: int                 # one FACTOR_ROW_TILE x slab tile
    rank_slabs: int
    chunks: int = 1

    @property
    def scheduled_tile_bytes(self) -> int:
        return self.scheduled_tiles * self.tile_bytes * self.rank_slabs

    @property
    def distinct_tile_bytes(self) -> int:
        return self.distinct_tiles * self.tile_bytes * self.rank_slabs

    @property
    def distinct_over_scheduled(self) -> float:
        """Fraction of scheduled fetches that are distinct (1.0 = no waste)."""
        return self.distinct_tiles / max(self.scheduled_tiles, 1)

    @property
    def scheduled_over_distinct(self) -> float:
        """The re-fetch factor the reorder pass attacks (≥ 1.0)."""
        return self.scheduled_tiles / max(self.distinct_tiles, 1)


def predict_stream_traffic(idx, valid, *, mode: int, rows_cap: int,
                           blk: int, tile_rows: int, rank: int,
                           factor_rows: Sequence[int],
                           row_offset: int = 0, gather_itemsize: int = 4,
                           ordering: str = "as-given",
                           max_chunk_bytes: int | None = None,
                           frow_tile: int = FACTOR_ROW_TILE
                           ) -> StreamTraffic:
    """Predict the stream kernel's tile traffic for a nonzero stream.

    A host-side (numpy) replication of ``ops.build_block_layout`` +
    ``_align_to_blocks`` on the index streams, followed by
    :func:`block_tile_analysis` — i.e. *exactly* the arithmetic the
    executor performs, on exactly the stream it would run, without
    touching a kernel. The input contract matches the executor's:
    ``idx (cap, N)`` with valid elements first and output-tile runs
    contiguous ascending (a row-sorted or ``repro.reorder``-ed stream).

    ``factor_rows`` is the per-input-mode factor row count (window
    bound). ``max_chunk_bytes`` replicates the executor's chunk
    budgeting (same :func:`chunk_boundaries` + :func:`stream_chunk_bytes`
    arithmetic), so the scheduled count includes the per-chunk window
    tightening the executor applies — the mechanism that turns a
    locality sort into counted byte savings. The returned
    :class:`StreamTraffic` carries the global tightened
    ``window_tiles`` — feed them to ``plan_residency(window_tiles=...)``
    to certify the stream rung under the *measured* window, and the
    predicted ``distinct/scheduled`` ratio the committed
    ``BENCH_reorder.json`` tracks before/after reordering.
    """
    idx = np.asarray(idx)
    valid = np.asarray(valid, bool)
    cap, nmodes = idx.shape
    in_modes = [w for w in range(nmodes) if w != mode]
    k = len(in_modes)
    assert len(factor_rows) == k, (factor_rows, k)
    num_tiles = rows_cap // tile_rows
    n_pad = ((cap + blk - 1) // blk) * blk + num_tiles * blk

    local_row = np.where(valid, idx[:, mode].astype(np.int64) - row_offset, 0)
    tile_of_elem = np.where(valid, local_row // tile_rows, num_tiles)
    counts = np.bincount(tile_of_elem[valid].astype(np.int64),
                         minlength=num_tiles)[:num_tiles]
    padded = ((counts + blk - 1) // blk) * blk
    offsets = np.concatenate([[0], np.cumsum(padded)]).astype(np.int64)
    first_of_tile = np.searchsorted(tile_of_elem, tile_of_elem, side="left")
    rank_in_tile = np.arange(cap, dtype=np.int64) - first_of_tile
    slot = np.where(valid, offsets[tile_of_elem] + rank_in_tile, n_pad)

    idx_in = np.where(valid[:, None], idx[:, in_modes], 0).astype(np.int64)
    aligned = np.zeros((n_pad + 1, k), np.int64)
    aligned[slot] = idx_in          # padding slots stay 0 -> tile 0
    per_block = (aligned[:n_pad] // frow_tile).reshape(-1, blk, k)
    _, _, _, distinct_counts = block_tile_analysis(per_block)

    windows = tuple(
        int(min(stream_window_tiles(blk, int(factor_rows[i])),
                max(1, int(distinct_counts[:, i].max()))))
        for i in range(k))
    num_blocks = per_block.shape[0]

    # The executor's chunking, replicated: tile_of_block from the block
    # layout's offsets, the chunk-byte budget, then per-chunk window
    # tightening — each chunk is its own kernel call whose static
    # schedule width only has to cover that chunk's blocks.
    block_start = np.arange(num_blocks, dtype=np.int64) * blk
    tile_of_block = np.clip(
        np.searchsorted(offsets, block_start, side="right") - 1,
        0, num_tiles - 1)
    if max_chunk_bytes is None:
        max_blocks = num_blocks
    else:
        max_blocks = max(
            1, max_chunk_bytes // stream_chunk_bytes(blk, k, windows))
    chunks = chunk_boundaries(tile_of_block, max_blocks)
    cwindows = chunk_window_tiles(distinct_counts, chunks, windows)
    scheduled = sum((stop - start) * sum(cw)
                    for (start, stop), cw in zip(chunks, cwindows))

    rpad = padded_rank(rank)
    return StreamTraffic(
        ordering=ordering,
        num_blocks=num_blocks,
        nnz=int(valid.sum()),
        window_tiles=windows,
        scheduled_tiles=int(scheduled),
        distinct_tiles=int(distinct_counts.sum()),
        tile_bytes=frow_tile * min(rpad, _kernel.RANK_SLAB)
        * gather_itemsize,
        rank_slabs=rpad // _kernel.RANK_SLAB,
        chunks=len(chunks),
    )
