"""jit'd wrappers: FLYCOO shard layout construction + Pallas MTTKRP dispatch.

``build_block_layout`` turns the sorted per-device nonzero stream into the
block-aligned layout the kernel requires (no block straddles an output row
tile — the runtime equivalent of FLYCOO's shard/super-shard alignment), then
``mttkrp_device_step`` runs gather → (fused) Hadamard → blocked scatter.

The runnable backends are the :data:`BACKENDS` tuple (``ref`` /
``pallas_fused`` / ``pallas_fused_tiled`` / ``pallas_fused_bf16`` /
``pallas_fused_gather`` / ``pallas_fused_gather_tiled`` /
``pallas_fused_gather_bf16`` / ``pallas_fused_gather_stream``), plus
``auto`` which resolves through :func:`select_backend`. **The full
backend decision matrix — per-backend traffic/VMEM characteristics, the
working-set formulas, and worked ``auto`` examples — lives in
``docs/kernels.md``;** this module deliberately doesn't duplicate that
table. Short version: ``auto`` picks the cheapest numerics-preserving
path whose residency the :mod:`repro.oocore.planner` can certify under
the VMEM budget (in-kernel gather → slab-streamed gather → out-of-core
row-streamed gather → fused → rank-tiled, with a segment-sum ``ref``
below the MXU-padding rank threshold and where no Pallas rung fits; the
gather family needs the factor sizes — ``factor_rows`` — to be
considered);
the bf16-gather variants (bf16 gathers, fp32 accumulate — halve gather
traffic, ≈(N−1)·2⁻⁸ rel. error) are opt-in only and never chosen by
``auto``.

(The plain-XLA ``segsum`` backend used by dry-runs lives one level up in
``core.distributed.device_mttkrp`` — it never reaches this module.)

Everything here is static-shape and jit-safe so it can live inside
``shard_map`` per device.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from . import kernel as _kernel
from . import ref as _ref
from ...obs import counters as _obs
from ...obs import scopes as _scopes
from ...obs import tracer as _tracer_mod
from ...oocore import planner as _planner
# Imported as the submodule path (not via the package __init__) so the
# reorder ↔ kernels import cycle resolves: ordering.py only needs
# ``kernel`` (already initialized when this module loads), and we only
# touch _reorder attributes at call time.
from ...reorder import ordering as _reorder
from ...resilience import faults as _faults
from ...resilience import policy as _resilience

__all__ = [
    "BACKENDS",
    "AUTO_BACKENDS",
    "GATHER_BACKENDS",
    "STREAM_BACKEND",
    "MIN_MXU_RANK",
    "MXU_RANK_MULTIPLE",
    "block_sources",
    "build_block_layout",
    "fused_fits_vmem",
    "gather_fits_vmem",
    "gather_stream_fits_vmem",
    "mttkrp_blocked",
    "mttkrp_device_step",
    "pad_rank",
    "runtime_blk",
    "select_backend",
    "step_traffic_bytes",
    "tile_schedule",
    "timed_device_step",
    "VMEM_BUDGET_BYTES",
]

# MXU lane width — rank padding multiple and the rank-slab width of the
# tiled kernel. Single source of truth in kernel.py.
MXU_RANK_MULTIPLE = _kernel.MXU_RANK_MULTIPLE

# Per-core VMEM working-set budget for the auto dispatch (half of a v5e
# core's ~128 MiB VMEM — same θ=0.5 cache-fraction stance as the paper's
# Eq. 3). Single source of truth in kernel.py (shared with the
# repro.oocore planner, which may be imported before this module).
VMEM_BUDGET_BYTES = _kernel.VMEM_BUDGET_BYTES

# Below this rank the one-hot MXU matmul pads R to MXU_RANK_MULTIPLE and
# wastes ≥ 16× of the array; the XLA segment-sum reference wins.
# (kernel.py owns it so dispatch and planner can never disagree.)
MIN_MXU_RANK = _kernel.MIN_MXU_RANK

# Backends this module can run (mttkrp_device_step / select_backend).
# docs/kernels.md's decision matrix is CI-checked against this tuple
# (tests/check_docs.py); ``segsum`` dispatches one level up in
# core.distributed and ``auto`` is the select_backend resolver, so
# neither appears here.
BACKENDS = (
    "ref",
    "pallas_fused",
    "pallas_fused_tiled",
    "pallas_fused_bf16",
    "pallas_fused_gather",
    "pallas_fused_gather_tiled",
    "pallas_fused_gather_bf16",
    "pallas_fused_gather_stream",
)

# What ``auto`` may resolve to (statically or via a calibration table):
# every BACKENDS member that preserves fp32 numerics. The bf16-gather
# variants trade accuracy for gather traffic and must be requested
# explicitly (backend string or DynasorRuntime.gather_dtype) — a timing
# table must never silently change numerics.
AUTO_BACKENDS = tuple(b for b in BACKENDS if not b.endswith("_bf16"))

# The in-kernel gather family mttkrp_device_step runs through the
# gather kernels (after the *_bf16 name is folded into gather_dtype):
# these skip the HBM materialization of gathered factor rows entirely.
GATHER_BACKENDS = ("pallas_fused_gather", "pallas_fused_gather_tiled")

# The out-of-core member of the gather family: factors stay HBM-resident
# and stream through a bounded VMEM tile window (``repro.oocore``).
STREAM_BACKEND = _kernel.STREAM_BACKEND_NAME


def pad_rank(x, multiple: int = MXU_RANK_MULTIPLE):
    """Pad the trailing (rank) dim to an MXU-aligned multiple."""
    r = x.shape[-1]
    pad = (-r) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
    return jnp.pad(x, widths)


# Static version of :func:`pad_rank` for dispatch arithmetic — aliased
# from kernel.py, the single source shared with the residency planner.
padded_rank = _kernel.padded_rank


def _pad_factor_rows(x, multiple: int):
    """Pad a factor's leading (row) dim to a whole number of stream tiles.

    The stream kernel DMAs ``FACTOR_ROW_TILE``-row tiles out of the
    HBM-resident factor, so its row count must divide evenly; padding
    rows are zero and unreachable (indices are < the true row count).
    """
    pad = (-x.shape[0]) % multiple
    if pad == 0:
        return x
    widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths)


def fused_fits_vmem(nmodes: int, rank: int, blk: int, tile_rows: int,
                    vmem_budget: int = VMEM_BUDGET_BYTES, *,
                    tiled: bool = False, gather_itemsize: int = 4) -> bool:
    """Hard feasibility: does a fused kernel's working set fit VMEM?

    Thin delegate to :func:`repro.oocore.planner.backend_fits` — the one
    residency authority every dispatch layer shares (static rule here,
    tuned planning in ``repro.tune.model``). A calibration table may
    *prefer* a fused backend, but never past this bound. ``tiled=True``
    budgets one ``RANK_SLAB``-wide slab instead of the full padded rank
    (the rank-tiled kernel's working set); ``gather_itemsize=2`` sizes
    the bf16-gather variants.
    """
    return _planner.backend_fits(
        "pallas_fused_tiled" if tiled else "pallas_fused",
        nmodes=nmodes, rank=rank, blk=blk, tile_rows=tile_rows,
        vmem_budget=vmem_budget, gather_itemsize=gather_itemsize)


def gather_fits_vmem(nmodes: int, rank: int, blk: int, tile_rows: int,
                     factor_rows: int, vmem_budget: int = VMEM_BUDGET_BYTES,
                     *, tiled: bool = False,
                     gather_itemsize: int = 4) -> bool:
    """Hard feasibility of the resident in-kernel gather family.

    ``factor_rows`` is the total row count of the N−1 replicated
    input-factor matrices (Σ I_pad over non-output modes) — the resident
    operand the gather kernels hold in VMEM. ``tiled=True`` budgets one
    ``RANK_SLAB``-wide column slab of each factor instead of the full
    padded rank (the slab-streamed regime); ``gather_itemsize=2`` sizes
    the bf16-gather variants. Delegates to the ``repro.oocore`` planner.
    """
    return _planner.backend_fits(
        "pallas_fused_gather_tiled" if tiled else "pallas_fused_gather",
        nmodes=nmodes, rank=rank, blk=blk, tile_rows=tile_rows,
        factor_rows=factor_rows, vmem_budget=vmem_budget,
        gather_itemsize=gather_itemsize)


def gather_stream_fits_vmem(nmodes: int, rank: int, blk: int,
                            tile_rows: int, factor_rows,
                            vmem_budget: int = VMEM_BUDGET_BYTES, *,
                            gather_itemsize: int = 4) -> bool:
    """Hard feasibility of the out-of-core row-streamed gather.

    Unlike the resident family, this scales with the *window* (``Σ_w
    min(blk, ceil(rows_w / FACTOR_ROW_TILE))`` tiles of 128 rows, one
    rank slab wide), not with the factor sizes — only the window must
    fit. ``factor_rows`` may be the aggregate int (conservative windows)
    or a per-input-mode sequence (exact). Delegates to the
    ``repro.oocore`` planner.
    """
    return _planner.backend_fits(
        STREAM_BACKEND, nmodes=nmodes, rank=rank, blk=blk,
        tile_rows=tile_rows, factor_rows=factor_rows,
        vmem_budget=vmem_budget, gather_itemsize=gather_itemsize)


def select_backend(
    backend: str,
    *,
    nmodes: int,
    rank: int,
    blk: int = 512,
    tile_rows: int = 128,
    vmem_budget: int = VMEM_BUDGET_BYTES,
    table=None,
    factor_rows=None,
    num_blocks: int | None = None,
) -> str:
    """Resolve ``auto`` to a concrete backend; pass others through.

    ``factor_rows`` describes the N−1 replicated input-factor matrices
    (rows over non-output modes) — the information the gather family's
    residency planning needs: an int total (Σ I_pad, the historical
    form), or a per-input-mode sequence (exact stream-window planning).
    ``None`` means the caller doesn't know the factor sizes (a purely
    shape-keyed dispatch query), and the gather family — the out-of-core
    streamed member included — is then never chosen: its feasibility
    cannot be certified. ``mttkrp_device_step`` always passes the
    per-mode sequence, so end-to-end ``auto`` prefers the gather family
    whenever it fits. ``num_blocks`` (the aligned stream's block count,
    also passed by ``mttkrp_device_step``) adds the streaming rung's SMEM
    check (:func:`repro.oocore.planner.backend_fits`).

    When a calibration ``table`` (a ``repro.tune`` ``CalibrationTable``
    or ``CostModel`` — anything with a ``best_backend`` method) is
    given, ``auto`` follows the *measured* argmin interpolated to this
    configuration instead of the static model below. The table is
    consulted duck-typed so this module never imports ``repro.tune``;
    if it cannot answer (no entries near this configuration) the static
    decision applies, bit-identical to the no-table path. Two hard
    constraints bound the table, preference never overrides them:

      * VMEM feasibility — every table answer is re-certified by the
        ``repro.oocore`` residency planner
        (:func:`repro.oocore.planner.backend_fits`): a fused/tiled
        choice whose working set exceeds ``vmem_budget``, or a gather
        choice (resident, slab-streamed or out-of-core row-streamed)
        whose residency cannot be certified (``factor_rows`` unknown, or
        over budget), is an extrapolation beyond the measured grid — it
        is discarded and the static decision applies;
      * numerics — the table is only consulted over :data:`AUTO_BACKENDS`,
        so a measured-fast bf16-gather variant never changes results
        behind ``auto``'s back.

    Static decision: the :func:`repro.oocore.planner.plan_residency`
    ladder (all static — safe to call under jit tracing; worked
    examples in ``docs/kernels.md``): ``ref`` below the MXU-padding rank
    threshold, else the first residency rung whose working set fits the
    budget — factors whole-VMEM (``pallas_fused_gather``) → one rank
    slab resident (``pallas_fused_gather_tiled``) → out-of-core tile
    window (``pallas_fused_gather_stream``; factors stay HBM-resident) →
    fused (``pallas_fused``) → rank-tiled fused (``pallas_fused_tiled``)
    → ``ref`` where not even one rank slab fits. Rungs that need the
    factor sizes are skipped when ``factor_rows`` is ``None``.
    """
    # Every resolution emits a ``dispatch.backend`` counter with the
    # decision *and* why (explicit | table | static). select_backend runs
    # at jit-trace time, so the count is once per unique static signature
    # per process — host-independent, which is what lets the obs baseline
    # gate pin dispatch decisions in CI.
    if backend != "auto":
        if backend not in BACKENDS:
            raise ValueError(
                f"unknown MTTKRP backend {backend!r}: expected 'auto' or "
                f"one of {BACKENDS} (the plain-XLA 'segsum' path is "
                "handled by core.distributed.device_mttkrp)")
        _obs.add("dispatch.backend", backend=backend, source="explicit")
        return backend
    if table is not None:
        # Below the MXU-padding threshold the table may only answer from
        # ranks it actually measured (a `covers` check, duck-typed like
        # best_backend) — clamped below-grid extrapolation must not
        # override the static rank<MIN_MXU_RANK -> ref rule.
        covers = getattr(table, "covers", None)
        rank_ok = rank >= MIN_MXU_RANK or (
            covers is not None and covers(nmodes=nmodes, rank=rank,
                                          blk=blk, tile_rows=tile_rows))
        choice = table.best_backend(
            nmodes=nmodes, rank=rank, blk=blk, tile_rows=tile_rows,
            allowed=AUTO_BACKENDS,
        ) if rank_ok else None
        if choice is not None and not _planner.backend_fits(
                choice, nmodes=nmodes, rank=rank, blk=blk,
                tile_rows=tile_rows, factor_rows=factor_rows,
                vmem_budget=vmem_budget, num_blocks=num_blocks):
            choice = None               # infeasible extrapolation
        if choice is not None:
            _obs.add("dispatch.backend", backend=choice, source="table")
            return choice
    chosen = _planner.plan_residency(
        nmodes=nmodes, rank=rank, blk=blk, tile_rows=tile_rows,
        factor_rows=factor_rows, vmem_budget=vmem_budget,
        num_blocks=num_blocks).backend
    _obs.add("dispatch.backend", backend=chosen, source="static")
    return chosen


def runtime_blk(g: int, *, compiled: bool) -> int:
    """Nonzero block a runtime uses for FLYCOO shard size ``g``.

    Compiled kernels need ``blk % COMPILED_BLK_MULTIPLE == 0`` (see
    ``kernel.py``), so they get exactly that; the interpreter keeps the
    shard-sized block, capped at 512.
    """
    if compiled:
        return _kernel.COMPILED_BLK_MULTIPLE
    return min(g, 512)


def n_pad_for(cap: int, rows_cap: int, blk: int, tile_rows: int) -> int:
    """Static aligned-stream length: every tile wastes < blk slots."""
    num_tiles = rows_cap // tile_rows
    return ((cap + blk - 1) // blk) * blk + num_tiles * blk


@functools.partial(
    jax.jit, static_argnames=("rows_cap", "blk", "tile_rows")
)
def build_block_layout(local_row, valid, *, rows_cap: int, blk: int,
                       tile_rows: int, order_keys=None):
    """Compute block-aligned slots for a sorted nonzero stream.

    Args:
      local_row: ``(cap,)`` int32 output row per element, ascending among
        valid elements; invalid elements trail. (Strictly: only the
        output-**tile** runs must be contiguous ascending — the order of
        elements within a tile run is free, which is the freedom the
        ``order_keys`` path spends.)
      valid: ``(cap,)`` bool.
      rows_cap: output rows (multiple of ``tile_rows``).
      order_keys: optional tuple of ``(cap,)`` int arrays (most
        significant first — ``repro.reorder.locality_keys``). When
        given, elements are ranked within their output-tile run by
        these keys instead of by stream position, so the aligned stream
        comes out locality-ordered *in-jit* — no host-side permutation,
        and the ordering survives the dynamic remapping between modes
        (which re-sorts by row every transition). With keys the input
        need not be sorted at all beyond valid-first: the lexsort
        groups the tile runs itself.

    Returns:
      ``(slot, tile_of_block)`` — ``slot[(cap,)]`` destination of each
      element in the aligned stream (``n_pad_for(...)`` = dump slot for
      invalid), ``tile_of_block[(n_pad//blk,)]`` non-decreasing output tile
      per block.

    Each tile run starts, in the (sorted or lexsorted) stream, at the
    exclusive cumsum of the per-tile counts, so an element's rank in its
    run is its position minus that start: no search over the stream.

    Runs under the ``layout`` named scope (:mod:`repro.obs.scopes`), so
    its device time can be read from a trace on every rung that calls it.
    """
    with jax.named_scope(_scopes.LAYOUT):
        cap = local_row.shape[0]
        num_tiles = rows_cap // tile_rows
        n_pad = n_pad_for(cap, rows_cap, blk, tile_rows)
        tile_of_elem, counts, offsets, starts = _tile_runs(
            local_row, valid, tile_rows=tile_rows, num_tiles=num_tiles,
            blk=blk)
        # slot = position - start + offset, so one table of
        # (offset - start) per tile carries both; its last entry (the
        # invalid elements' tile) is never used.
        if order_keys:
            # Position = rank under the (tile, keys, position) lexsort — the
            # jit twin of the host-side repro.reorder.locality_lexsort (same
            # keys, same tiebreak, so the two produce bit-identical aligned
            # streams). The lexsort orders by tile first, so its tile runs
            # start at the same ``starts``.
            order = _locality_order(tile_of_elem, order_keys)
            position = jnp.zeros(cap, jnp.int32).at[order].set(
                jnp.arange(cap, dtype=jnp.int32))
        else:
            # Elements sorted by (valid desc, row asc) => per-tile runs
            # contiguous, in tile order.
            position = jnp.arange(cap, dtype=jnp.int32)
        slot = jnp.where(
            valid,
            position + jnp.take(offsets - starts, tile_of_elem, fill_value=0),
            n_pad,
        )
        return slot, _tile_of_block(offsets, n_pad, blk, num_tiles)


def _tile_runs(local_row, valid, *, tile_rows: int, num_tiles: int,
               blk: int):
    """Each element's output tile (``num_tiles`` for an invalid one), and
    per tile its valid-element ``counts``, its first slot in the aligned
    stream (``offsets``: runs padded to whole blocks) and its run's first
    position in the sorted stream (``starts``). Both tables have one
    entry past the last tile."""
    tile_of_elem = jnp.where(valid, local_row // tile_rows, num_tiles)
    counts = jax.ops.segment_sum(
        valid.astype(jnp.int32), tile_of_elem,
        num_segments=num_tiles + 1)[:num_tiles]
    padded = ((counts + blk - 1) // blk) * blk
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(padded).astype(jnp.int32)])
    # Run starts: tile t's run begins after every valid element of the
    # tiles before it.
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(counts).astype(jnp.int32)])
    return tile_of_elem, counts, offsets, starts


def _locality_order(tile_of_elem, order_keys):
    """Stream positions in (tile, keys, position) lexsort order."""
    pos = jnp.arange(tile_of_elem.shape[0], dtype=jnp.int32)
    keys = tuple(jnp.asarray(kk).astype(jnp.int32) for kk in order_keys)
    return jnp.lexsort((pos,) + keys[::-1] + (tile_of_elem,))


def _tile_of_block(offsets, n_pad: int, blk: int, num_tiles: int):
    """Output tile of each aligned block, non-decreasing."""
    block_start = jnp.arange(n_pad // blk, dtype=jnp.int32) * blk
    return jnp.clip(
        jnp.searchsorted(offsets, block_start, side="right") - 1,
        0, num_tiles - 1,
    ).astype(jnp.int32)


@functools.partial(
    jax.jit, static_argnames=("rows_cap", "blk", "tile_rows")
)
def block_sources(local_row, valid, *, rows_cap: int, blk: int,
                  tile_rows: int, order_keys=None):
    """Where each block of the aligned stream takes its elements from.

    The gather-side twin of :func:`build_block_layout`, same arguments
    and the same aligned stream: tile t's run of ``counts[t]`` elements,
    from ``starts[t]`` of the (sorted or lexsorted) stream, fills the
    slots from ``offsets[t]`` in order. So block ``b`` holds the
    ``count[b]`` consecutive elements from stream position
    ``src_start[b]`` and padding after them, and aligning a stream
    column is one gather of ``blk``-wide windows
    (:func:`_gather_blocks`), with no per-element scatter.

    Returns ``(order, src_start, count, tile_of_block)``, the last three
    ``(n_pad // blk,)`` int32. ``order`` is ``None`` without
    ``order_keys``; with them it is the lexsort order the stream must be
    taken in before the windows apply.

    Runs under the ``layout`` named scope, as :func:`build_block_layout`.
    """
    with jax.named_scope(_scopes.LAYOUT):
        num_tiles = rows_cap // tile_rows
        n_pad = n_pad_for(local_row.shape[0], rows_cap, blk, tile_rows)
        tile_of_elem, counts, offsets, starts = _tile_runs(
            local_row, valid, tile_rows=tile_rows, num_tiles=num_tiles,
            blk=blk)
        order = (_locality_order(tile_of_elem, order_keys)
                 if order_keys else None)
        tile_of_block = _tile_of_block(offsets, n_pad, blk, num_tiles)
        # Blocks past the last run read as the last tile's, with
        # ``within`` past its count: they hold nothing.
        within = (jnp.arange(n_pad // blk, dtype=jnp.int32) * blk
                  - jnp.take(offsets, tile_of_block))
        src_start = jnp.take(starts, tile_of_block) + within
        count = jnp.clip(jnp.take(counts, tile_of_block) - within, 0, blk)
        return order, src_start, count, tile_of_block


def _gather_blocks(x, src_start, count, blk: int):
    """Lay the ``(k, cap)`` stream columns ``x`` down in aligned slots:
    block ``b`` is ``x[:, src_start[b]:src_start[b] + count[b]]``, zero
    after. One gather of ``(k, blk)`` windows; ``x`` is padded by one
    block so no window is clamped. Returns ``(k, n_pad)``."""
    k = x.shape[0]
    xp = jnp.pad(x, ((0, 0), (0, blk)))
    win = jax.vmap(
        lambda s: jax.lax.dynamic_slice_in_dim(xp, s, blk, axis=1),
        out_axes=1)(src_start)                          # (k, blocks, blk)
    keep = jnp.arange(blk, dtype=jnp.int32)[None, :] < count[:, None]
    return jnp.where(keep[None], win, 0).reshape(k, -1)


def _align_to_blocks(x, slot, n_pad: int):
    """Scatter ``(cap, ...)`` stream rows into their block-aligned slots.

    Slot ``n_pad`` is the dump row for invalid elements; it is allocated and
    then sliced off, so invalid entries vanish regardless of their payload.
    """
    out_shape = (n_pad + 1,) + x.shape[1:]
    return jnp.zeros(out_shape, x.dtype).at[slot].set(x)[:-1]


# Slots per step of the rank-major Khatri-Rao build: its gathered
# (chunk, R) rows are lane-padded to 128 in HBM, so the build walks the
# stream in chunks rather than hold N−1 such stream-long arrays at once.
KRP_CHUNK_SLOTS = 1 << 18


def _khatri_rao_rows_t(v_al, idx_al, factors, chunk: int = KRP_CHUNK_SLOTS):
    """``out[:, s] = v_al[s] · ∏_w factors[w][idx_al[w, s]]`` for each
    aligned slot ``s``, rank-major.

    ``v_al`` is ``(n_pad,)`` and ``idx_al`` ``(N−1, n_pad)``, laid down in
    slots (:func:`_gather_blocks`), zero at padding slots. Returns
    ``(krp_rows(R), n_pad)`` float32 (zero rows below R, zero columns at
    padding slots), the operand
    :func:`~repro.kernels.mttkrp.kernel.krp_scatter` takes. The product
    starts from the fp32 value and multiplies the gathered rows in mode
    order, as the gather kernels do, so every rung forms the same
    contributions bit for bit.
    """
    n = v_al.shape[0]
    rank = factors[0].shape[-1]
    rows = _kernel.krp_rows(rank)

    def block(lo, size):
        c = jax.lax.dynamic_slice_in_dim(v_al, lo, size)[:, None]
        for f, ix in zip(factors, idx_al):
            c = c * jnp.take(f, jax.lax.dynamic_slice_in_dim(ix, lo, size),
                             axis=0)
        return jnp.pad(c.T, ((0, rows - rank), (0, 0)))

    if n <= chunk:
        return block(0, n)
    full = n // chunk
    out = jax.lax.fori_loop(
        0, full,
        lambda i, acc: jax.lax.dynamic_update_slice_in_dim(
            acc, block(i * chunk, chunk), i * chunk, axis=1),
        jnp.zeros((rows, n), jnp.float32))
    if n % chunk:
        out = out.at[:, full * chunk:].set(block(full * chunk, n % chunk))
    return out


def tile_schedule(indices_aligned, blk: int, window: int,
                  frow_tile: int = _kernel.FACTOR_ROW_TILE):
    """Per-block factor-tile schedule for the out-of-core stream kernel.

    ``indices_aligned`` is one mode's block-aligned ``(n_pad,)`` int32
    factor-row stream. Returns a ``(n_pad // blk, window)`` int32 array:
    row ``b`` holds the sorted distinct ``frow_tile``-row factor tiles
    block ``b``'s nonzeros touch, padded (by repeating the first tile)
    up to ``window`` slots. Correct whenever ``window >=`` the block's
    distinct-tile count — guaranteed for ``window = min(blk,
    ceil(rows / frow_tile))`` (``planner.stream_window_tiles``), since a
    block holds ``blk`` nonzeros and a factor only has that many tiles.
    jit-safe (static shapes throughout); this is the schedule the
    kernel's BlockSpec index maps consume via scalar prefetch.
    """
    tiles = (indices_aligned // frow_tile).astype(jnp.int32)
    per_block = tiles.reshape(-1, blk)
    num_blocks = per_block.shape[0]
    st = jnp.sort(per_block, axis=1)
    first = jnp.concatenate(
        [jnp.ones((num_blocks, 1), bool), st[:, 1:] != st[:, :-1]], axis=1)
    rank_of = jnp.cumsum(first, axis=1) - 1            # distinct rank
    # Scatter each first occurrence to its rank; duplicates go to a dump
    # column that is sliced off. Unfilled slots keep the block's first
    # (smallest) tile so padding never schedules a tile the window
    # wouldn't otherwise hold.
    dest = jnp.where(first, rank_of, window)
    sched = jnp.broadcast_to(st[:, :1], (num_blocks, window + 1))
    sched = sched.at[jnp.arange(num_blocks)[:, None], dest].set(st)
    return sched[:, :window].astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("rows_cap", "blk", "tile_rows", "interpret", "use_ref"),
)
def mttkrp_blocked(contrib, local_row, valid, *, rows_cap: int,
                   blk: int = 512, tile_rows: int = 128,
                   interpret: bool | None = None, use_ref: bool = False):
    """Scatter stage on a sorted stream via the Pallas kernel.

    ``use_ref=True`` routes to the pure-jnp oracle (A/B testing and the
    CPU-bench path).
    """
    if use_ref:
        masked = jnp.where(valid[:, None], contrib, 0.0)
        row = jnp.where(valid, local_row, 0)
        return _ref.segment_accumulate_ref(masked, row, rows_cap)

    _, src_start, count, tile_of_block = block_sources(
        local_row, valid, rows_cap=rows_cap, blk=blk, tile_rows=tile_rows)
    rank = contrib.shape[-1]
    krp_t = _gather_blocks(contrib.astype(jnp.float32).T, src_start, count,
                           blk)
    krp_t = jnp.pad(krp_t, ((0, _kernel.krp_rows(rank) - rank), (0, 0)))
    row_aligned = _gather_blocks(
        (local_row % tile_rows).astype(jnp.int32)[None], src_start, count,
        blk)[0]
    out = _kernel.krp_scatter(
        krp_t, row_aligned, tile_of_block,
        rows_cap=rows_cap, blk=blk, tile_rows=tile_rows, interpret=interpret,
    )
    return out[:, :rank]


@functools.partial(
    jax.jit,
    static_argnames=("mode", "rows_cap", "blk", "tile_rows", "interpret",
                     "backend", "gather_dtype", "ordering"),
)
def mttkrp_device_step(idx, val, valid, factors, *, mode: int, rows_cap: int,
                       row_offset, blk: int = 512, tile_rows: int = 128,
                       interpret: bool | None = None,
                       backend: str = "pallas_fused",
                       gather_dtype: str = "float32",
                       ordering: str = "none"):
    """Full per-device mode step: gather → Hadamard → blocked scatter.

    Args:
      idx: ``(cap, N)`` permuted coordinates of owned nonzeros, sorted by
        output row (valid first).
      val: ``(cap,)`` values (0 on padding).
      valid: ``(cap,)`` bool.
      factors: list of ``(I_pad_w, R)`` replicated factor matrices (permuted
        row space).
      mode: output mode.
      rows_cap: owned output rows.
      row_offset: scalar — first owned permuted row (``device_id*rows_cap``).
      interpret: ``None`` (default) defers to the
        :mod:`repro.runtime.execution` policy (interpret / compiled /
        auto); a bool forces the Pallas interpreter (True) or Mosaic
        compilation (False) for this call.
      backend: one of :data:`BACKENDS` or ``auto`` (decision matrix in
        ``docs/kernels.md``).
      gather_dtype: ``"float32"`` | ``"bfloat16"`` — dtype every Pallas
        rung gathers factor rows in (the accumulate is always fp32).
        In-kernel gathers hold the resident factor matrices — or the
        streamed tile windows of ``pallas_fused_gather_stream`` — in
        bf16; the row-gathering rungs take bf16 rows in XLA. The
        ``pallas_fused_bf16`` / ``pallas_fused_gather_bf16`` backend
        names are the untiled rungs with this forced on (so a plain
        backend-string API can reach them). ``ref`` ignores it.
      ordering: :data:`repro.reorder.ORDERINGS` policy. Anything but
        ``"none"`` re-ranks nonzeros *within* each output-row-tile run
        by the gathered modes' factor-tile locality keys (in-jit, via
        ``build_block_layout``'s ``order_keys`` path) before block
        alignment, shrinking the stream backend's per-block tile
        schedules. Applied to every Pallas rung (same aligned stream
        everywhere ⇒ A/B bit-exactness across backends is preserved
        per ordering); ``ref`` does not block-align, so it ignores it.

    Returns ``(rows_cap, R)`` float32 local output factor rows.
    """
    # Validate before dispatch: non-fused resolutions never read
    # gather_dtype, and a typo must not pass silently on those paths.
    if gather_dtype not in ("float32", "bfloat16"):
        raise ValueError(
            f"unknown gather_dtype {gather_dtype!r}: expected "
            "'float32' or 'bfloat16'")
    _reorder.validate_ordering(ordering)
    nmodes = idx.shape[1]
    rank = factors[mode].shape[-1]
    in_modes = [w for w in range(nmodes) if w != mode]
    backend = select_backend(
        backend, nmodes=nmodes, rank=rank, blk=blk, tile_rows=tile_rows,
        factor_rows=tuple(factors[w].shape[0] for w in in_modes),
        num_blocks=n_pad_for(idx.shape[0], rows_cap, blk, tile_rows) // blk,
    )

    def _dispatch(backend: str, interpret, gather_dtype=gather_dtype):
        if backend == "pallas_fused_bf16":
            backend, gather_dtype = "pallas_fused", "bfloat16"
        if backend == "pallas_fused_gather_bf16":
            backend, gather_dtype = "pallas_fused_gather", "bfloat16"
        local_row = (idx[:, mode] - row_offset).astype(jnp.int32)
        local_row = jnp.where(valid, local_row, 0)
        if backend == "ref":
            ell = jnp.where(valid, val, 0.0)[:, None].astype(factors[0].dtype)
            for w in in_modes:
                ell = ell * jnp.take(factors[w], idx[:, w], axis=0)
            return mttkrp_blocked(
                ell.astype(jnp.float32), local_row, valid, rows_cap=rows_cap,
                blk=blk, tile_rows=tile_rows, use_ref=True)

        gdt = jnp.bfloat16 if gather_dtype == "bfloat16" else jnp.float32
        vals = jnp.where(valid, val, 0.0)
        n_pad = n_pad_for(local_row.shape[0], rows_cap, blk, tile_rows)
        idx_cols = [jnp.where(valid, idx[:, w], 0).astype(jnp.int32)
                    for w in in_modes]
        # max_rows is static (factor shapes), so host-side sorts derive the
        # identical Morton bit budget — and huge modes widen the key words
        # instead of clamping tile ids.
        order_keys = _reorder.locality_keys(
            jnp.stack(idx_cols, axis=1), ordering,
            max_rows=max(factors[w].shape[0] for w in in_modes))
        if backend in GATHER_BACKENDS + (STREAM_BACKEND,):
            slot, tile_of_block = build_block_layout(
                local_row, valid, rows_cap=rows_cap, blk=blk,
                tile_rows=tile_rows, order_keys=order_keys,
            )
            # In-kernel gather: no per-factor take, no _align_to_blocks of
            # R-wide rows — only the int32 index stream is block-aligned,
            # and the replicated factor matrices go to the kernel whole.
            # Padding/invalid slots point at factor row 0 (in-bounds
            # gather; their value is 0 so the contribution vanishes).
            # Casting the resident matrices to the gather dtype is what
            # halves both the VMEM residency and the factor-load traffic
            # for bf16 (same values as the row-gathering rungs'
            # cast-then-take). Mode-major (K, n_pad): each mode's stream
            # lane-dense.
            v_al = _align_to_blocks(vals, slot, n_pad)
            r_al = _align_to_blocks(
                (local_row % tile_rows).astype(jnp.int32), slot, n_pad)
            idx_al = jnp.stack([_align_to_blocks(c, slot, n_pad)
                                for c in idx_cols])
            fmats = tuple(pad_rank(factors[w].astype(gdt))
                          for w in in_modes)
            if backend == STREAM_BACKEND:
                # Out-of-core: factors stay HBM-resident; the kernel
                # streams FACTOR_ROW_TILE-row tiles through a bounded
                # VMEM window, driven by the per-block tile schedule.
                # Window widths are the planner's static correctness
                # bound, so this path is jit-safe for any index data.
                frow = _kernel.FACTOR_ROW_TILE
                fmats = tuple(_pad_factor_rows(f, frow) for f in fmats)
                scheds = tuple(
                    tile_schedule(
                        idx_al[i], blk,
                        _planner.stream_window_tiles(blk, f.shape[0]))
                    for i, f in enumerate(fmats))
                out = _kernel.fused_mttkrp_nmode_gather_stream(
                    v_al, idx_al, fmats, r_al, tile_of_block, scheds,
                    rows_cap=rows_cap, blk=blk, tile_rows=tile_rows,
                    interpret=interpret,
                )
                return out[:, :rank]
            kern = (_kernel.fused_mttkrp_nmode_gather_tiled
                    if backend == "pallas_fused_gather_tiled"
                    else _kernel.fused_mttkrp_nmode_gather)
            out = kern(
                v_al, idx_al, fmats, r_al, tile_of_block,
                rows_cap=rows_cap, blk=blk, tile_rows=tile_rows,
                interpret=interpret,
            )
            return out[:, :rank]

        # Row-gathering rungs (pallas_fused, pallas_fused_tiled):
        # each slot's Khatri-Rao row val·∏_w A_w[idx_w] is formed in XLA
        # — the gather kernels' arithmetic, in their order — and laid
        # down rank-major, so a mode step holds one (krp_rows(R), n_pad)
        # operand whatever N is. The kernel only scatters it; the tiled
        # rung does so one RANK_SLAB of columns per pass. The stream's
        # scalar columns reach their slots as one gather of block-wide
        # windows; the factor rows are then taken in slot order.
        order, src_start, count, tile_of_block = block_sources(
            local_row, valid, rows_cap=rows_cap, blk=blk,
            tile_rows=tile_rows, order_keys=order_keys)
        with jax.named_scope(_scopes.ROW_GATHER):
            cols = jnp.stack(
                [jax.lax.bitcast_convert_type(vals.astype(jnp.float32),
                                              jnp.int32), *idx_cols,
                 (local_row % tile_rows).astype(jnp.int32)])
            if order is not None:
                cols = jnp.take(cols, order, axis=1)
            al = _gather_blocks(cols, src_start, count, blk)
            krp_t = _khatri_rao_rows_t(
                jax.lax.bitcast_convert_type(al[0], jnp.float32), al[1:-1],
                [factors[w].astype(gdt) for w in in_modes])
        out = _kernel.krp_scatter(
            krp_t, al[-1], tile_of_block, rows_cap=rows_cap, blk=blk,
            tile_rows=tile_rows, interpret=interpret,
            rank_slab=(_kernel.RANK_SLAB if backend == "pallas_fused_tiled"
                       else None),
        )
        return out[:, :rank]

    def _attempt(backend: str, interpret):
        # Registered failure boundary (repro.resilience): this is
        # where lowering failures and VMEM OOM surface (at trace
        # time under jit — a fault here aborts the trace, leaving
        # no cache entry, so a retry re-dispatches for real).
        _faults.fault_site("ops.kernel")
        return _dispatch(backend, interpret)

    pol = _resilience.get_policy()
    if pol is None:
        # No active policy: fail fast — exactly the pre-resilience
        # dispatch, one attempt at the selected backend.
        return _attempt(backend, interpret)
    return pol.dispatch(_attempt, backend, interpret)


def step_traffic_bytes(*, cap: int, nmodes: int, rank: int, rows_cap: int,
                       gather_dtype: str = "float32") -> int:
    """First-order counted traffic model of one device mode step.

    What the step minimally moves, independent of backend: the nonzero
    stream (values + local rows + K gathered-mode indices, 4 B each),
    one gathered factor row per nonzero per input mode (``rpad``
    gather-dtype elements), and the output factor write. Deliberately a
    *model*, not a measurement — it is the denominator-side constant the
    roofline divides a measured step time by (``ops.step.model_bytes``),
    playing the role the oocore path's exact schedule-counted bytes play
    for the stream backend.
    """
    k = nmodes - 1
    gi = 2 if gather_dtype == "bfloat16" else 4
    rpad = padded_rank(rank)
    stream_b = cap * (4 + 4 + 4 * k)
    gather_b = cap * k * rpad * gi
    out_b = rows_cap * rpad * 4
    return stream_b + gather_b + out_b


def timed_device_step(idx, val, valid, factors, *, mode: int, rows_cap: int,
                      row_offset, blk: int = 512, tile_rows: int = 128,
                      interpret: bool | None = None,
                      backend: str = "pallas_fused",
                      gather_dtype: str = "float32",
                      ordering: str = "none"):
    """:func:`mttkrp_device_step`, fenced and timed from the host.

    The device step itself is jitted — no host clock can live inside
    it — so wall-clock observability needs this one-call-out wrapper:
    an ``ops.device_step`` span around the call plus
    ``block_until_ready``, with the step's modeled traffic
    (:func:`step_traffic_bytes`) emitted *inside* the span so the
    roofline can join measured seconds with counted bytes. Emits
    ``ops.step_s`` (wall seconds, labeled by backend). The backend label
    is the *requested* backend (``auto`` stays ``auto``): resolving it
    here would re-emit the dispatch counters the jitted step already
    emits at trace time.
    """
    tracer = _tracer_mod.get_tracer()
    cap = int(idx.shape[0])
    nmodes = int(idx.shape[1])
    rank = int(factors[mode].shape[-1])
    model_b = step_traffic_bytes(cap=cap, nmodes=nmodes, rank=rank,
                                 rows_cap=rows_cap,
                                 gather_dtype=gather_dtype)
    t0 = time.perf_counter()
    with tracer.span("ops.device_step", backend=backend, mode=mode,
                     ordering=ordering):
        _obs.add("ops.step.model_bytes", model_b, backend=backend)
        out = mttkrp_device_step(
            idx, val, valid, factors, mode=mode, rows_cap=rows_cap,
            row_offset=row_offset, blk=blk, tile_rows=tile_rows,
            interpret=interpret, backend=backend,
            gather_dtype=gather_dtype, ordering=ordering)
        out = jax.block_until_ready(out)
    _obs.add("ops.step_s", time.perf_counter() - t0, backend=backend)
    return out
