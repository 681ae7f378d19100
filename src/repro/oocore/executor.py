"""Chunked out-of-core spMTTKRP execution over the streaming kernel.

The kernels bound their *VMEM* working set, but a mode step still
materializes its block-aligned operand streams whole: ``O(n_pad)``
values/rows/indices (plus, for the materializing fused family,
``O(n_pad·R̂)`` of gathered rows). For nonzero streams that outgrow a
host/HBM working-set budget this module is the next level of the same
out-of-core idea: split the FLYCOO stream into **row-tile-aligned
chunks** of whole nonzero blocks, run every chunk through the same
kernel, and thread the running accumulator through each call's
``out_init`` so the result reproduces the single-pass accumulation
order **bit-exactly** — chunking is a pure re-bracketing of the very
same additions, never a re-ordering.

Chunk boundaries prefer output-row-tile edges (a tile's run of blocks
stays within one chunk, so most tiles are touched by exactly one chunk);
when a single tile's run alone exceeds the budget the split lands
mid-tile, which the ``out_init`` threading makes exact anyway.

:func:`mttkrp_out_of_core` is the entry point; it uses
:func:`repro.oocore.planner.plan_residency` for the window geometry and
returns counted DMA-traffic statistics (`StreamStats`) next to the
result — the numbers ``benchmarks/bench_oocore.py`` records.
"""
from __future__ import annotations

import dataclasses
import math
import time as _time

import jax.numpy as jnp
import numpy as np

from ..kernels.mttkrp import kernel as _kernel
from ..kernels.mttkrp import ops as _ops
from ..obs import counters as _obs
from ..obs import tracer as _tracer_mod
from ..reorder import ordering as _reorder
from ..resilience import faults as _faults
from ..resilience import policy as _resilience
from . import planner as _planner

__all__ = [
    "StreamStats",
    "chunk_boundaries",
    "mttkrp_out_of_core",
]


@dataclasses.dataclass(frozen=True)
class StreamStats:
    """Counted traffic of one chunked out-of-core mode step.

    All byte counts are *counted* from the actual tile schedules (not
    timed): what the kernel's DMA engine is asked to move. The three
    tile-fetch counts bound each other —
    ``pipelined <= scheduled`` and ``distinct <= scheduled`` —
    ``scheduled`` being the naive every-slot-every-block cost,
    ``distinct`` what the schedule actually references (padding slots
    repeat a block's first tile), and ``pipelined`` what survives the
    Pallas revolving-buffer rule (a slot whose tile index is unchanged
    from the previous grid step is not re-fetched).
    """

    backend: str
    chunks: int
    num_blocks: int
    nnz: int                        # valid nonzeros
    blk: int
    rank_padded: int
    rank_slabs: int
    window_tiles: tuple[int, ...]   # per input mode
    chunk_block_counts: tuple[int, ...]
    scheduled_tile_bytes: int
    distinct_tile_bytes: int
    pipelined_tile_bytes: int
    index_stream_bytes: int         # vals + rows + K index streams, per slab
    window_vmem_bytes: int          # resident window per grid step
    resident_equiv_vmem_bytes: int  # what whole-factor residency would need
    # repro.reorder: the locality policy the stream was permuted with
    # ("none" = as given), and the counted cost the *unsorted* stream
    # would have paid — predicted by planner.predict_stream_traffic
    # before the permutation, so before/after is one mode step's worth
    # of data, not two runs. 0 when ordering is "none".
    ordering: str = "none"
    presort_scheduled_tile_bytes: int = 0
    presort_distinct_tile_bytes: int = 0

    @property
    def tile_bytes_per_nnz(self) -> float:
        return self.pipelined_tile_bytes / max(self.nnz, 1)

    @property
    def index_bytes_per_nnz(self) -> float:
        return self.index_stream_bytes / max(self.nnz, 1)

    @property
    def scheduled_over_distinct(self) -> float:
        """The tile re-fetch factor (≥ 1.0) the reorder pass attacks."""
        return self.scheduled_tile_bytes / max(self.distinct_tile_bytes, 1)

    @property
    def presort_scheduled_over_distinct(self) -> float:
        """Same ratio for the stream as it arrived (before reordering)."""
        return (self.presort_scheduled_tile_bytes
                / max(self.presort_distinct_tile_bytes, 1))


# Chunk planning lives in the planner (so predict_stream_traffic can
# replicate it without a circular import); re-exported here because this
# module is where chunks are *executed*.
chunk_boundaries = _planner.chunk_boundaries


def _schedule_fetch_stats(scheds, chunks, chunk_windows, frow_tile: int,
                          slab_cols: int, num_slabs: int, gi: int,
                          distinct_counts) -> tuple[int, int, int]:
    """Counted (scheduled, distinct, pipelined) tile-fetch bytes.

    Counts exactly what the chunk loop issues: each chunk's schedule is
    sliced to that chunk's tightened window widths, so ``scheduled`` is
    Σ_chunks blocks · Σ_modes w_chunk — the same arithmetic
    ``planner.predict_stream_traffic`` performs, which is why predicted
    and counted bytes agree exactly.
    """
    tile_bytes = frow_tile * slab_cols * gi
    scheduled = sum((stop - start) * sum(cw)
                    for (start, stop), cw in zip(chunks, chunk_windows))
    distinct = sum(int(d.sum()) for d in distinct_counts)
    pipelined = 0
    for i, s in enumerate(scheds):
        s = np.asarray(s)
        for (start, stop), cw in zip(chunks, chunk_windows):
            c = s[start:stop, :cw[i]]
            if len(c) == 0:
                continue
            pipelined += c.shape[1]                       # first block: all
            if len(c) > 1:
                pipelined += int((c[1:] != c[:-1]).sum())  # slot changed
    return (scheduled * tile_bytes * num_slabs,
            distinct * tile_bytes * num_slabs,
            pipelined * tile_bytes * num_slabs)


def mttkrp_out_of_core(
    idx, val, valid, factors, *, mode: int, rows_cap: int, row_offset=0,
    blk: int = 128, tile_rows: int = 128,
    vmem_budget: int = _planner.VMEM_BUDGET_BYTES,
    max_chunk_bytes: int | None = None,
    gather_dtype: str = "float32",
    interpret: bool | None = None,
    ordering: str = "none",
):
    """One mode step, out-of-core: streamed factor tiles + chunked blocks.

    Same data contract as ``ops.mttkrp_device_step`` (sorted-by-output-row
    stream, trailing invalids, replicated factor matrices), executed
    through ``fused_mttkrp_nmode_gather_stream`` in chunks:

      * the factor matrices stay HBM-resident; per input mode the kernel
        holds a bounded window of ``FACTOR_ROW_TILE``-row tiles in VMEM
        (widths from :func:`planner.plan_residency`, tightened to the
        measured per-block distinct-tile maximum — the executor sees the
        data, so unlike the jit dispatch it doesn't need the worst-case
        bound);
      * the block stream is split by :func:`chunk_boundaries` so no
        chunk's aligned operand arrays (values + rows + index streams +
        schedules) exceed ``max_chunk_bytes`` (``None`` = one chunk);
      * each chunk's kernel call receives the previous accumulator as
        ``out_init`` — the summation order is identical to the unchunked
        kernel, so the result is **bit-exact** against the resident
        gather backend for any chunk split.

    ``ordering`` (a ``repro.reorder`` policy) permutes the stream
    host-side for factor-tile locality before alignment: the counted
    cost of the stream *as it arrived* is predicted first
    (``planner.predict_stream_traffic`` — the same arithmetic as the
    count below, so it is exact) and recorded in the stats'
    ``presort_*`` fields; the run then pays the post-sort cost. The
    result stays bit-exact **per stream** (streamed ≡ resident on the
    same permuted stream); against the unsorted stream it differs only
    by fp32 accumulation order.

    Returns ``(out, stats)`` — ``out`` is ``(rows_cap, R)`` float32,
    ``stats`` a :class:`StreamStats` of counted DMA traffic.
    """
    if gather_dtype not in ("float32", "bfloat16"):
        raise ValueError(f"unknown gather_dtype {gather_dtype!r}")
    _reorder.validate_ordering(ordering)
    gdt = jnp.bfloat16 if gather_dtype == "bfloat16" else jnp.float32
    gi = 2 if gather_dtype == "bfloat16" else 4
    frow = _kernel.FACTOR_ROW_TILE
    nmodes = np.asarray(idx).shape[1]
    in_modes = [w for w in range(nmodes) if w != mode]
    k = len(in_modes)
    rank = factors[mode].shape[-1]
    rpad = _ops.padded_rank(rank)
    num_slabs = rpad // _kernel.RANK_SLAB

    presort_scheduled_b = presort_distinct_b = 0
    if ordering != "none":
        traffic_kw = dict(
            mode=mode, rows_cap=rows_cap, blk=blk, tile_rows=tile_rows,
            rank=rank,
            factor_rows=tuple(int(factors[w].shape[0]) for w in in_modes),
            row_offset=int(row_offset), gather_itemsize=gi,
            max_chunk_bytes=max_chunk_bytes)
        pre = _planner.predict_stream_traffic(
            idx, valid, ordering="none", **traffic_kw)
        presort_scheduled_b = pre.scheduled_tile_bytes
        presort_distinct_b = pre.distinct_tile_bytes
        idx, val, valid, _ = _reorder.reorder_stream(
            idx, val, valid, mode=mode, ordering=ordering,
            tile_rows=tile_rows, row_offset=int(row_offset),
            max_rows=max(int(factors[w].shape[0]) for w in in_modes))
    idx = jnp.asarray(idx)
    val = jnp.asarray(val)
    valid = jnp.asarray(valid)

    # Block-aligned streams, exactly like the in-jit gather paths.
    local_row = (idx[:, mode] - row_offset).astype(jnp.int32)
    local_row = jnp.where(valid, local_row, 0)
    n_pad = _ops.n_pad_for(local_row.shape[0], rows_cap, blk, tile_rows)
    slot, tile_of_block = _ops.build_block_layout(
        local_row, valid, rows_cap=rows_cap, blk=blk, tile_rows=tile_rows)
    v_al = _ops._align_to_blocks(jnp.where(valid, val, 0.0), slot, n_pad)
    r_al = _ops._align_to_blocks(
        (local_row % tile_rows).astype(jnp.int32), slot, n_pad)
    idx_in = jnp.stack([idx[:, w] for w in in_modes], axis=1)
    idx_in = jnp.where(valid[:, None], idx_in, 0).astype(jnp.int32)
    idx_al = _ops._align_to_blocks(idx_in, slot, n_pad)
    fmats = tuple(
        _ops._pad_factor_rows(_ops.pad_rank(jnp.asarray(factors[w]).astype(gdt)),
                              frow)
        for w in in_modes)

    # Window widths: the planner's static bound, tightened by the data.
    # One sorted-distinct analysis serves the window sizing, the tile
    # schedules and the fetch statistics (ops.tile_schedule re-derives
    # the same thing jit-side for the in-jit dispatch path; out here the
    # data is already on host, so doing it once in numpy is the cheap
    # route for streams long enough to need chunking).
    tiles_np = np.asarray(idx_al) // frow                 # (n_pad, K)
    per_block = tiles_np.reshape(-1, blk, k)
    st, first, rank_of, dcounts = _planner.block_tile_analysis(per_block)
    distinct_counts = [dcounts[:, i] for i in range(k)]
    windows = tuple(
        int(min(_planner.stream_window_tiles(blk, int(fmats[i].shape[0])),
                max(1, int(distinct_counts[i].max()))))
        for i in range(k))
    num_blocks_total = st.shape[0]
    scheds = []
    for i in range(k):
        width = windows[i]
        # Same construction as ops.tile_schedule: first occurrences
        # scatter to their distinct rank, duplicates to a dump column,
        # unfilled slots keep the block's first (smallest) tile.
        dest = np.where(first[:, :, i], rank_of[:, :, i], width)
        sched = np.broadcast_to(
            st[:, :1, i], (num_blocks_total, width + 1)).copy()
        sched[np.arange(num_blocks_total)[:, None], dest] = st[:, :, i]
        scheds.append(jnp.asarray(sched[:, :width].astype(np.int32)))
    scheds = tuple(scheds)

    # Chunking: bound each chunk's aligned-operand bytes, then tighten
    # every chunk's schedule width to its own blocks' distinct-tile
    # maximum. Each chunk is a separate kernel call with its own static
    # width, so the slice is free — and it is where a repro.reorder
    # locality sort cashes in: post-sort, almost every chunk's window
    # collapses to 1–2 while only the rare-tile tail pays the wide one.
    # (Slicing columns [w_c, width) off a schedule is safe: distinct
    # ranks occupy columns [0, d) with d <= w_c; everything past that is
    # padding repeating the block's first tile.)
    num_blocks = n_pad // blk
    if max_chunk_bytes is None:
        max_blocks = num_blocks
    else:
        max_blocks = max(
            1, max_chunk_bytes // _planner.stream_chunk_bytes(blk, k, windows))
    chunks = chunk_boundaries(tile_of_block, max_blocks)
    cwindows = _planner.chunk_window_tiles(dcounts, chunks, windows)

    # Counted traffic is fully determined by the schedules — build the
    # stats *before* launching so they can be recorded inside the
    # mode_step span below: the byte deltas then land in that span's
    # self_counters, which is the join the achieved-bandwidth roofline
    # (repro.obs.prof.roofline) reads. Registry totals are unchanged.
    slab_cols = min(rpad, _kernel.RANK_SLAB)
    scheduled_b, distinct_b, pipelined_b = _schedule_fetch_stats(
        scheds, chunks, cwindows, frow, slab_cols, num_slabs, gi,
        distinct_counts)
    stats = StreamStats(
        backend=_planner.STREAM_BACKEND,
        chunks=len(chunks),
        num_blocks=num_blocks,
        nnz=int(np.asarray(valid).sum()),
        blk=blk,
        rank_padded=rpad,
        rank_slabs=num_slabs,
        window_tiles=windows,
        chunk_block_counts=tuple(stop - start for start, stop in chunks),
        scheduled_tile_bytes=scheduled_b,
        distinct_tile_bytes=distinct_b,
        pipelined_tile_bytes=pipelined_b,
        index_stream_bytes=num_slabs * n_pad * (4 + 4 + 4 * k),
        window_vmem_bytes=_kernel.gather_stream_vmem_bytes(
            k, rpad, blk, tile_rows, windows, gather_itemsize=gi),
        resident_equiv_vmem_bytes=_kernel.gather_vmem_bytes(
            k, rpad, blk, tile_rows,
            sum(int(f.shape[0]) for f in fmats), gather_itemsize=gi),
        ordering=ordering,
        presort_scheduled_tile_bytes=presort_scheduled_b,
        presort_distinct_tile_bytes=presort_distinct_b,
    )

    tracer = _tracer_mod.get_tracer()
    out = jnp.zeros((rows_cap, rpad), jnp.float32)
    t_step = _time.perf_counter()
    with tracer.span("oocore.mode_step", mode=mode, chunks=len(chunks),
                     backend=_planner.STREAM_BACKEND, rung="stream",
                     ordering=ordering):
        # Emitted inside the span so the oocore.dma.* / reorder.dma.*
        # deltas attach to it (the tracer diffs the registry per span).
        _obs.record_stream_stats(stats)
        for ci, (start, stop) in enumerate(chunks):
            sl = slice(start * blk, stop * blk)
            cw = cwindows[ci]
            with tracer.span("oocore.chunk", chunk=ci,
                             blocks=stop - start):
                def _launch(out=out, sl=sl, start=start, stop=stop, cw=cw):
                    # Registered failure boundary (repro.resilience):
                    # one chunk = one bounded DMA window + kernel
                    # launch — the unit a transient blip costs, and
                    # the unit the retry policy replays.
                    _faults.fault_site("oocore.chunk")
                    return _kernel.fused_mttkrp_nmode_gather_stream(
                        v_al[sl], idx_al[sl].T, fmats, r_al[sl],
                        tile_of_block[start:stop],
                        tuple(s[start:stop, :cw[i]]
                              for i, s in enumerate(scheds)),
                        rows_cap=rows_cap, blk=blk, tile_rows=tile_rows,
                        interpret=interpret, out_init=out)

                pol = _resilience.get_policy()
                out = (_launch() if pol is None
                       else pol.run("oocore.chunk", _launch))
                if tracer.enabled:
                    out = out.block_until_ready()
    _obs.add("oocore.mode_step_s", _time.perf_counter() - t_step,
             backend=_planner.STREAM_BACKEND, ordering=ordering)
    return out[:, :rank], stats
