"""Pallas TPU kernels: blocked segmented MTTKRP accumulation.

TPU-native adaptation of the paper's elementwise gather–Hadamard–scatter
(Alg. 2 lines 13-25). The FLYCOO *shard* (``g`` nonzeros, cache-sized)
becomes the VMEM nonzero block; the *super-shard* row interval becomes the
output row tile; and — the key rethinking for the MXU — the random scatter
into output rows becomes a **one-hot matmul**:

    out_tile (T×R)  +=  onehot(local_row, T)ᵀ (T×B)  @  contrib (B×R)

which is dense, layout-friendly and runs on the systolic array. Correctness
relies on the FLYCOO invariant that nonzeros are sorted by output row and
blocks are padded to never straddle a row tile (ops.py builds that layout),
so the sequential TPU grid revisits each output tile over a contiguous run
of blocks and accumulates in VMEM.

Kernel matrix (see ops.py for the dispatch layer that picks between them):

  ==========================  =============================================
  kernel                      contract
  ==========================  =============================================
  ``segment_accumulate``      scatter-only: takes an HBM-materialized
                              ``contrib (B×R)`` block per grid step. Pays
                              2·R·4 B/nonzero of HBM traffic (write + read
                              of ``contrib``) that the fused kernels avoid.
  ``fused_mttkrp_nmode``      gather-Hadamard-scatter for **any** tensor
                              order: takes N−1 gathered factor-row blocks
                              and forms ``contrib = val ⊙ ⊙_w rows_w``
                              entirely in VMEM (loop over input modes inside
                              the kernel body). ``contrib`` never exists in
                              HBM.
  ``fused_mttkrp_nmode_tiled``  the same gather-Hadamard-scatter with a
                              second grid axis over ``RANK_SLAB``-wide rank
                              slabs: each grid step holds only one slab of
                              the N−1 factor blocks / contrib / out tile,
                              so the VMEM working set is independent of R
                              and the fused traffic win survives arbitrary
                              rank (the scalar streams are re-read once per
                              slab — the only extra cost).
  ``fused_mttkrp_3mode``      **deprecated alias** (warns): the 3-mode
                              special case of the N-mode kernel.
  ``fused_mttkrp_nmode_gather``  gather **inside the kernel**: takes the
                              full replicated factor matrices (VMEM-resident
                              across grid steps) plus a block-aligned
                              ``(N−1, n_pad)`` int32 index stream, and forms
                              each nonzero's factor rows in the body
                              (one-hot MXU matmul when compiled — the
                              ``gather`` primitive has no Mosaic lowering —
                              ``jnp.take`` in the interpreter; bitwise
                              identical). The gathered operands never
                              exist in HBM at all — the per-nonzero stream
                              shrinks from ``(N−1)·R̂·4`` B of rows to
                              ``(N−1)·4`` B of indices.
  ``fused_mttkrp_nmode_gather_tiled``  the in-kernel gather composed with
                              the rank-slab grid axis: only one
                              ``RANK_SLAB``-wide column slab of each factor
                              is resident per slab pass, so the resident
                              set is ``ΣI_pad·RANK_SLAB·gi`` instead of
                              ``ΣI_pad·R̂·gi`` (the index/scalar streams are
                              re-read once per slab).
  ``fused_mttkrp_nmode_gather_stream``  **out-of-core** in-kernel gather:
                              the factor matrices stay HBM-resident and the
                              Pallas pipeline DMAs ``FACTOR_ROW_TILE``-row
                              factor tiles into a per-mode window of
                              ``window_tiles`` VMEM slots, double-buffered
                              across grid steps and driven by a
                              scalar-prefetched per-block *tile schedule*
                              derived from the nonzero index stream. VMEM
                              holds ``Σ W_w·128·slab·gi`` of factor data
                              instead of ``ΣI_pad·…`` — arbitrarily large
                              factor dimensions stream through a bounded
                              window (composes with the rank-slab axis).
  ==========================  =============================================

Both fused kernels accept bf16 factor-row operands (``ops.py``'s
``pallas_fused_bf16`` backend / ``gather_dtype="bfloat16"``): the Hadamard
product is accumulated in fp32 inside the kernel regardless, so bf16 only
halves the *gathered-operand* footprint and HBM gather traffic.

Grid: one step per nonzero block. ``tile_of_block`` is scalar-prefetched and
drives the output BlockSpec index_map. The output aliases an ``out_init``
operand (zeros by default) via ``input_output_aliases``: tiles no block
visits keep it, and the first block of each tile's run copies its init
tile into the VMEM accumulator (a compiled kernel never reads output
blocks back from HBM — see ``_accumulate``).

Execution mode: every entry point takes ``interpret: bool | None``.
``None`` — the default everywhere — resolves through
:mod:`repro.runtime.execution`, the session-wide
interpret / compiled / auto policy with capability probing; a bool is an
explicit per-call override (the lowering harness passes ``False``).
Compiled (Mosaic) geometry constraint: the rank-1 ``(blk,)`` scalar-stream
blocks require ``blk`` to be a multiple of ``COMPILED_BLK_MULTIPLE`` (1024;
the interpreter accepts any ``blk``), and every ``pallas_call`` asks for
``VMEM_LIMIT_BYTES`` of scoped VMEM. ``tests/test_lowering.py`` lowers
every kernel wrapper with ``interpret=False`` and
``tests/test_tpu_compile.py`` compiles the main path for a described
v5e, to keep the compiled path honest on CPU-only hosts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "MXU_RANK_MULTIPLE",
    "RANK_SLAB",
    "FACTOR_ROW_TILE",
    "segment_accumulate",
    "fused_mttkrp_nmode",
    "fused_mttkrp_nmode_tiled",
    "fused_mttkrp_nmode_gather",
    "fused_mttkrp_nmode_gather_tiled",
    "fused_mttkrp_nmode_gather_stream",
    "fused_mttkrp_3mode",
    "fused_vmem_bytes",
    "fused_tiled_vmem_bytes",
    "gather_vmem_bytes",
    "gather_tiled_vmem_bytes",
    "gather_stream_vmem_bytes",
]

# MXU lane width: the rank dimension is padded to a multiple of this for the
# one-hot scatter matmul, and the rank-tiled kernel slabs the rank axis in
# exactly this width. The single source of truth — ops.py (``pad_rank`` /
# ``padded_rank`` / the rank<8 MXU-padding guard) and tune/model.py derive
# from it rather than re-hardcoding 128.
MXU_RANK_MULTIPLE = 128

# Width of one rank slab in ``fused_mttkrp_nmode_tiled`` — one MXU lane tile.
RANK_SLAB = MXU_RANK_MULTIPLE

# Row height of one streamed factor tile in the out-of-core gather kernel:
# the unit the Pallas pipeline DMAs from the HBM-resident factor into a
# VMEM window slot. 128 rows = 16 fp32 sublane tiles — big enough that a
# tile fetch is one long coalesced burst, small enough that a window of a
# few slots stays far under the VMEM budget. ``repro.oocore`` derives all
# its tile arithmetic from this constant.
FACTOR_ROW_TILE = 128

# Below this rank the one-hot MXU matmul pads R to MXU_RANK_MULTIPLE and
# wastes ≥ 16× of the array; the XLA segment-sum reference wins. Lives
# here (the only module with no intra-repo imports) so ops.py and
# repro.oocore.planner alias one definition instead of each other —
# either may be imported first.
MIN_MXU_RANK = MXU_RANK_MULTIPLE // 16

# Per-core VMEM working-set budget for residency planning (half of a
# v5e core's ~128 MiB VMEM — same θ=0.5 cache-fraction stance as the
# paper's Eq. 3). Same single-source rationale as MIN_MXU_RANK.
VMEM_BUDGET_BYTES = 64 * 1024 * 1024

# Scoped-VMEM limit every kernel asks Mosaic for. Pallas double-buffers
# each blocked operand, so a step whose working set the planner
# certified under VMEM_BUDGET_BYTES may need up to twice that; the
# compiler's own default limit (far below the budget) would otherwise
# decide what compiles.
VMEM_LIMIT_BYTES = 2 * VMEM_BUDGET_BYTES - 16 * 1024 * 1024

# Scalar-prefetch (SMEM) budget for residency planning: half of a v5e
# core's 1 MiB SMEM. Every scalar-prefetched operand (``tile_of_block``,
# the stream kernel's tile schedules) is held whole in SMEM.
SMEM_BUDGET_BYTES = 512 * 1024

# Compiled-path block rule: XLA lays rank-1 int32/fp32 arrays out in
# 1024-element tiles on TPU, and Mosaic refuses a (blk,) block whose
# tiling does not match, so compiled kernels need ``blk % 1024 == 0``.
# The interpreter accepts any ``blk``.
COMPILED_BLK_MULTIPLE = 1024

# Factor rows one step of the compiled one-hot gather covers (see
# ``_gather_rows``).
GATHER_CHUNK_ROWS = 512

# Dispatch-level name of the out-of-core streaming kernel
# (fused_mttkrp_nmode_gather_stream) in ops.BACKENDS.
STREAM_BACKEND_NAME = "pallas_fused_gather_stream"


def padded_rank(rank: int, multiple: int = MXU_RANK_MULTIPLE) -> int:
    """R rounded up to the MXU lane multiple — static dispatch arithmetic.

    The one definition (ops.py and repro.oocore.planner alias it, like
    the constants above) so feasibility math can never desynchronize
    between the dispatch and the residency planner.
    """
    return rank + (-rank) % multiple


def fused_vmem_bytes(num_in_modes: int, rank_padded: int, blk: int,
                     tile_rows: int, itemsize: int = 4,
                     gather_itemsize: int | None = None,
                     index_stream_modes: int = 0) -> int:
    """VMEM working set of one ``fused_mttkrp_nmode`` grid step.

    N−1 gathered factor-row blocks + the in-register ``contrib`` block +
    the one-hot scatter matrix + the resident output tile + the scalar
    streams. ops.py's ``auto`` dispatch compares this against the
    per-core VMEM budget.

    ``gather_itemsize`` sizes only the gathered factor-row blocks (2 for
    the bf16-gather variant); contrib / one-hot / out tile always
    accumulate at ``itemsize`` (fp32).

    The scalar-stream term is explicit about its members: the fp32
    values block and the int32 local-row block (both 4 B/element, hence
    ``2·blk·4``), plus — for the gather-in-kernel family, which streams
    its factor indices instead of pre-gathered rows —
    ``index_stream_modes`` int32 index blocks of ``blk`` elements each
    (``index_stream_modes = N−1``; 0 for the kernels whose operands are
    already gathered).
    """
    gi = itemsize if gather_itemsize is None else gather_itemsize
    factor_blocks = num_in_modes * blk * rank_padded * gi
    contrib_block = blk * rank_padded * itemsize
    onehot = blk * tile_rows * itemsize
    out_tile = tile_rows * rank_padded * itemsize
    scalars = (2 + index_stream_modes) * blk * itemsize
    return factor_blocks + contrib_block + onehot + out_tile + scalars


def fused_tiled_vmem_bytes(num_in_modes: int, rank_padded: int, blk: int,
                           tile_rows: int, rank_slab: int = RANK_SLAB,
                           itemsize: int = 4,
                           gather_itemsize: int | None = None) -> int:
    """VMEM working set of one ``fused_mttkrp_nmode_tiled`` grid step.

    Identical to :func:`fused_vmem_bytes` with the rank axis clamped to one
    slab — the whole point of the tiled kernel is that this is independent
    of R, so the fused path never has to fall back to the HBM-materialized
    kernel on rank growth.
    """
    return fused_vmem_bytes(
        num_in_modes, min(rank_padded, rank_slab), blk, tile_rows,
        itemsize=itemsize, gather_itemsize=gather_itemsize)


def gather_vmem_bytes(num_in_modes: int, rank_padded: int, blk: int,
                      tile_rows: int, factor_rows: int, itemsize: int = 4,
                      gather_itemsize: int | None = None) -> int:
    """VMEM working set of one ``fused_mttkrp_nmode_gather`` grid step.

    The replicated input-factor matrices themselves are the resident
    operands (``factor_rows`` = Σ I_pad over the N−1 input modes), and
    the per-nonzero streams are scalars only: values, local rows, and
    one int32 factor index per input mode. ``gather_itemsize`` sizes the
    resident matrices (2 for bf16 gathers); contrib / one-hot / out tile
    always accumulate at ``itemsize`` (fp32).
    """
    gi = itemsize if gather_itemsize is None else gather_itemsize
    resident_factors = factor_rows * rank_padded * gi
    return resident_factors + fused_vmem_bytes(
        0, rank_padded, blk, tile_rows, itemsize=itemsize,
        index_stream_modes=num_in_modes)


def gather_tiled_vmem_bytes(num_in_modes: int, rank_padded: int, blk: int,
                            tile_rows: int, factor_rows: int,
                            rank_slab: int = RANK_SLAB, itemsize: int = 4,
                            gather_itemsize: int | None = None) -> int:
    """VMEM working set of one ``fused_mttkrp_nmode_gather_tiled`` step.

    :func:`gather_vmem_bytes` with the rank axis clamped to one slab:
    only a ``rank_slab``-wide column slab of each factor matrix is
    resident per slab pass, so very large R cannot push the resident
    factors past the budget — only very large factor dimensions can.
    """
    return gather_vmem_bytes(
        num_in_modes, min(rank_padded, rank_slab), blk, tile_rows,
        factor_rows, itemsize=itemsize, gather_itemsize=gather_itemsize)


def gather_stream_vmem_bytes(num_in_modes: int, rank_padded: int, blk: int,
                             tile_rows: int, window_tiles,
                             frow_tile: int = FACTOR_ROW_TILE,
                             rank_slab: int = RANK_SLAB, itemsize: int = 4,
                             gather_itemsize: int | None = None) -> int:
    """VMEM working set of one ``fused_mttkrp_nmode_gather_stream`` step.

    The factors are *not* resident: per input mode only ``window_tiles``
    slots of ``frow_tile`` factor rows are held in VMEM (one rank slab
    wide — the stream kernel always composes with the rank-slab axis).
    ``window_tiles`` may be a single int applied to every input mode or
    a per-mode sequence. The scalar-prefetched schedules live in SMEM
    (the body reads them scalar-by-scalar) and — like ``tile_of_block``
    in every other kernel's accounting — are not counted here.
    """
    gi = itemsize if gather_itemsize is None else gather_itemsize
    if isinstance(window_tiles, int):
        window_tiles = (window_tiles,) * num_in_modes
    assert len(window_tiles) == num_in_modes, (window_tiles, num_in_modes)
    slab = min(rank_padded, rank_slab)
    windows = sum(w * frow_tile * slab * gi for w in window_tiles)
    return windows + fused_vmem_bytes(
        0, slab, blk, tile_rows, itemsize=itemsize,
        index_stream_modes=num_in_modes)


def _split_bf16(x):
    """fp32 ``x`` as ``(hi, mid, lo)`` bf16 parts with ``(hi+mid)+lo == x``.

    Each part keeps the next 8 significant bits of the remainder, so
    three cover fp32's 24. Non-fp32 input (bf16 factors) is one part.
    """
    if x.dtype != jnp.float32:
        return (x,)
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _onehot_dot(onehot, x, dimension_numbers):
    """``onehot ⋅ x`` on the MXU with ``x`` carried at full fp32 precision.

    At the MXU's default precision an fp32 operand is rounded to bf16.
    A 0/1 ``onehot`` is exact in bf16, so ``x`` is split into three bf16
    parts (:func:`_split_bf16`) and each part's product — exact, fp32
    accumulate — is summed: every output is then the fp32 sum of the
    selected ``x`` entries, without Mosaic's much slower fp32 matmul.
    """
    parts = _split_bf16(x)
    out = None
    for part in parts:
        d = jax.lax.dot_general(
            onehot.astype(part.dtype), part,
            dimension_numbers=dimension_numbers,
            preferred_element_type=jnp.float32)
        out = d if out is None else out + d
    return out


def _scatter_update(rows, contrib, tile_rows: int):
    """One-hot MXU scatter: ``(T×B) @ (B×R)`` update for the output tile."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (rows.shape[0], tile_rows), 1)
    onehot = rows[:, None] == iota
    return _onehot_dot(onehot, contrib, (((0,), (0,)), ((), ())))


def _onehot_rows(matrix, idx, base):
    """``onehot(idx - base) @ matrix``: rows ``base …`` of one chunk.

    The one-hot is built transposed, ``(I×B)``, so the lane-major index
    row broadcasts along sublanes with no relayout, and the matmul
    contracts its leading axis.
    """
    iota = jax.lax.broadcasted_iota(
        jnp.int32, (matrix.shape[0], idx.shape[0]), 0) + base
    return _onehot_dot(iota == idx[None, :], matrix,
                       (((0,), (0,)), ((), ())))


def _gather_rows(matrix, idx, *, onehot: bool, chunk_rows: int | None = None):
    """In-kernel row gather: ``out[i] = matrix[idx[i]]`` → ``(B, R)`` fp32.

    Two bitwise-identical implementations behind one switch:

      * ``onehot=False`` — ``jnp.take``. The cheap form (O(B) work) the
        interpreter runs, but the ``gather`` primitive it lowers to has
        no Pallas TPU (Mosaic) lowering rule.
      * ``onehot=True`` — the MXU form, the gather mirror of
        :func:`_scatter_update`: ``onehot(idx, I) (B×I) @ matrix (I×R)``
        on the systolic array. This is what the compiled path uses.
        With ``chunk_rows``, ``matrix`` is a VMEM ref read
        ``chunk_rows`` rows at a time in a loop, so the one-hot block and
        the kernel's VMEM stay bounded however tall the factor is.

    Equivalence is exact for in-range indices and any finite data: each
    output row is ``1.0·matrix[idx[i]]`` plus exact ``+0.0`` terms, and a
    bf16 ``matrix`` promotes to fp32 losslessly (the ``take`` form's
    bf16 rows promote identically at the Hadamard multiply) — so
    interpret and compiled execution stay bit-exact against each other.
    tests/test_lowering.py locks the equivalence down.
    """
    if not onehot:
        return jnp.take(matrix[...], idx, axis=0)
    rows = matrix.shape[0]
    if chunk_rows is None or rows <= chunk_rows:
        return _onehot_rows(matrix[...], idx, 0)
    full = rows // chunk_rows

    def body(c, acc):
        base = pl.multiple_of(c * chunk_rows, chunk_rows)
        part = matrix[pl.ds(base, chunk_rows), :]
        return acc + _onehot_rows(part, idx, base)

    acc = jax.lax.fori_loop(
        0, full, body,
        jnp.zeros((idx.shape[0], matrix.shape[1]), jnp.float32))
    if rows % chunk_rows:
        base = full * chunk_rows
        acc = acc + _onehot_rows(matrix[base:, :], idx, base)
    return acc


def _resolve_interpret(interpret, blk: int):
    """Resolve a per-call ``interpret`` override against the session policy.

    ``None`` — the default on every kernel entry point — defers to
    :mod:`repro.runtime.execution` (the one ``execution_mode`` switch:
    interpret / compiled / auto with capability probing). A bool is an
    explicit per-call override and wins. The policy module is imported
    lazily so this module keeps its no-import-time-intra-repo-deps
    property (ops.py and the oocore planner both alias its constants and
    may be imported in either order).

    A compiled call with a ``blk`` Mosaic would refuse raises here, with
    the rule, instead of deep inside the compiler.
    """
    if interpret is None:
        from ...runtime import execution as _execution
        interpret = _execution.default_interpret()
    interpret = bool(interpret)
    if not interpret and blk % COMPILED_BLK_MULTIPLE:
        raise ValueError(
            f"blk={blk} is not a multiple of {COMPILED_BLK_MULTIPLE}: "
            "compiled (Mosaic) kernels need rank-1 (blk,) blocks that "
            "match XLA's 1024-element tiling")
    return interpret


def _compiler_params():
    """Mosaic options shared by every kernel (ignored when interpreting)."""
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


@functools.partial(
    jax.jit, static_argnames=("rows_cap", "blk", "tile_rows", "interpret")
)
def segment_accumulate(
    contrib,
    local_row_in_tile,
    tile_of_block,
    *,
    rows_cap: int,
    blk: int = 512,
    tile_rows: int = 128,
    interpret: bool | None = None,
):
    """Blocked segmented accumulation (scatter stage of spMTTKRP).

    Args:
      contrib: ``(num_blocks*blk, R)`` block-aligned contributions; padding
        rows are zero. R should be a multiple of 128 for MXU alignment
        (ops.py pads).
      local_row_in_tile: ``(num_blocks*blk,)`` int32 row *within its tile*
        (``0 <= r < tile_rows``); padding points at row 0 with zero contrib.
      tile_of_block: ``(num_blocks,)`` int32 output tile per block,
        non-decreasing (FLYCOO sort order).
      rows_cap: total output rows (multiple of tile_rows).

    Returns:
      ``(rows_cap, R)`` float32 accumulated output.
    """
    interpret = _resolve_interpret(interpret, blk)
    n_pad, rank = contrib.shape
    assert n_pad % blk == 0, (n_pad, blk)
    assert rows_cap % tile_rows == 0, (rows_cap, tile_rows)
    num_blocks = n_pad // blk

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,              # tile_of_block
        grid=(num_blocks,),
        in_specs=[
            pl.BlockSpec((blk,), lambda b, tiles: (b,)),          # local_row
            pl.BlockSpec((blk, rank), lambda b, tiles: (b, 0)),   # contrib
            pl.BlockSpec((tile_rows, rank),
                         lambda b, tiles: (tiles[b], 0)),         # out_init alias
        ],
        out_specs=pl.BlockSpec((tile_rows, rank),
                               lambda b, tiles: (tiles[b], 0)),
    )
    out_init = jnp.zeros((rows_cap, rank), dtype=jnp.float32)
    return pl.pallas_call(
        functools.partial(_accum_body_aliased, tile_rows=tile_rows),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows_cap, rank), jnp.float32),
        input_output_aliases={3: 0},        # out_init -> out (indices incl. prefetch)
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(tile_of_block, local_row_in_tile, contrib, out_init)


def _accumulate(tile_ref, init_ref, out_ref, update, *, block_axis: int):
    """``out tile += update``, starting each tile's run of blocks from init.

    A compiled kernel never reads its output blocks back from HBM: the
    VMEM output buffer holds whatever the pipeline last left there. So
    the first block of each output tile's (contiguous, FLYCOO-sorted)
    run copies the aliased ``out_init`` tile in before accumulating —
    zeros, or the running accumulator of a chunked executor. Tiles no
    block visits keep the aliased content untouched.
    """
    b = pl.program_id(block_axis)
    first = (b == 0) | (tile_ref[b] != tile_ref[jnp.maximum(b - 1, 0)])

    @pl.when(first)
    def _():
        out_ref[...] = init_ref[...]

    out_ref[...] += update.astype(out_ref.dtype)


def _accum_body_aliased(tile_ref, row_ref, contrib_ref, init_ref, out_ref,
                        *, tile_rows: int):
    """Scatter-only body: accumulate one materialized ``contrib`` block."""
    update = _scatter_update(row_ref[...], contrib_ref[...], tile_rows)
    _accumulate(tile_ref, init_ref, out_ref, update, block_axis=0)


def _fused_nmode_body(*refs, tile_rows: int, block_axis: int):
    """Fused Hadamard (Alg. 2 lines 19-23) + scatter, any tensor order.

    Ref layout (positional, after scalar prefetch): ``tile_ref, row_ref,
    val_ref, rows_0 … rows_{K-1}, init_ref, out_ref`` where K = N−1 input
    modes. ``contrib`` is built by looping ``contrib *= rows_w`` over the
    gathered factor-row blocks — entirely in VMEM, never in HBM. The
    factor blocks may be bf16 (the bf16-gather variant); ``contrib``
    starts fp32 so every product accumulates at fp32.

    The same body serves the untiled and the rank-tiled kernel: the
    BlockSpecs decide whether a ref covers the full padded rank or one
    ``RANK_SLAB`` column slab, and the arithmetic is columnwise
    independent either way.
    """
    tile_ref, row_ref, val_ref = refs[0], refs[1], refs[2]
    factor_refs = refs[3:-2]
    init_ref, out_ref = refs[-2], refs[-1]
    rows = row_ref[...]
    contrib = val_ref[...][:, None].astype(jnp.float32)
    for rows_w in factor_refs:
        contrib = contrib * rows_w[...]
    update = _scatter_update(rows, contrib, tile_rows)
    _accumulate(tile_ref, init_ref, out_ref, update, block_axis=block_axis)


@functools.partial(
    jax.jit, static_argnames=("rows_cap", "blk", "tile_rows", "interpret")
)
def fused_mttkrp_nmode(
    vals,
    factor_rows,
    local_row_in_tile,
    tile_of_block,
    *,
    rows_cap: int,
    blk: int = 512,
    tile_rows: int = 128,
    interpret: bool | None = None,
    out_init=None,
):
    """N-mode fused variant: Hadamard product formed in VMEM, never in HBM.

    Saves 2·R·4 bytes/nonzero of HBM traffic vs. ``segment_accumulate`` on a
    pre-materialized ``contrib`` (the §Perf memory-term optimization), for a
    tensor of **any** order.

    Args:
      vals: ``(num_blocks*blk,)`` block-aligned nonzero values; padding is 0.
      factor_rows: tuple/list of K = N−1 arrays, each ``(num_blocks*blk, R)``
        — the gathered input-factor rows per nonzero, block-aligned with
        ``vals``. R must be identical across operands (a multiple of
        ``MXU_RANK_MULTIPLE`` for MXU alignment; ops.py pads). fp32 or
        bf16 — the Hadamard product always accumulates at fp32.
      local_row_in_tile: ``(num_blocks*blk,)`` int32 row within its tile.
      tile_of_block: ``(num_blocks,)`` int32 output tile per block,
        non-decreasing.
      rows_cap: total output rows (multiple of tile_rows).
      out_init: optional ``(rows_cap, R)`` float32 accumulator to add
        into (aliased — the kernel's output starts from it). ``None``
        means zeros. ``repro.oocore``'s chunked executor threads the
        running accumulator through here so splitting a stream into
        chunks reproduces the single-pass accumulation order bit-exactly.

    Returns:
      ``(rows_cap, R)`` float32 accumulated output.
    """
    interpret = _resolve_interpret(interpret, blk)
    factor_rows = tuple(factor_rows)
    assert factor_rows, "need at least one input-factor operand"
    n_pad, rank = factor_rows[0].shape
    for fr in factor_rows:
        assert fr.shape == (n_pad, rank), (fr.shape, (n_pad, rank))
    assert n_pad % blk == 0, (n_pad, blk)
    assert rows_cap % tile_rows == 0, (rows_cap, tile_rows)
    num_blocks = n_pad // blk
    n_in = len(factor_rows)

    in_specs = (
        [
            pl.BlockSpec((blk,), lambda b, tiles: (b,)),          # local_row
            pl.BlockSpec((blk,), lambda b, tiles: (b,)),          # vals
        ]
        + [
            pl.BlockSpec((blk, rank), lambda b, tiles: (b, 0))    # rows_w
            for _ in range(n_in)
        ]
        + [
            pl.BlockSpec((tile_rows, rank),
                         lambda b, tiles: (tiles[b], 0)),         # out_init alias
        ]
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_blocks,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile_rows, rank),
                               lambda b, tiles: (tiles[b], 0)),
    )
    if out_init is None:
        out_init = jnp.zeros((rows_cap, rank), dtype=jnp.float32)
    return pl.pallas_call(
        functools.partial(_fused_nmode_body, tile_rows=tile_rows,
                          block_axis=0),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows_cap, rank), jnp.float32),
        # out_init -> out; operand index counts prefetch + row/val + factors.
        input_output_aliases={3 + n_in: 0},
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(tile_of_block, local_row_in_tile, vals, *factor_rows, out_init)


@functools.partial(
    jax.jit,
    static_argnames=("rows_cap", "blk", "tile_rows", "rank_slab",
                     "interpret"),
)
def fused_mttkrp_nmode_tiled(
    vals,
    factor_rows,
    local_row_in_tile,
    tile_of_block,
    *,
    rows_cap: int,
    blk: int = 512,
    tile_rows: int = 128,
    rank_slab: int = RANK_SLAB,
    interpret: bool | None = None,
    out_init=None,
):
    """Rank-tiled N-mode fused variant: VMEM working set independent of R.

    Same contract as :func:`fused_mttkrp_nmode` with one extra
    precondition — R must be a multiple of ``rank_slab`` (ops.py's
    ``pad_rank`` guarantees this; padding columns are zero and sliced
    off by the caller). The grid gains a *major* axis over rank slabs:

        grid = (R // rank_slab, num_blocks)

    so for each slab the kernel makes a full pass over the nonzero
    stream, holding only ``(blk, rank_slab)`` factor/contrib blocks and a
    ``(tile_rows, rank_slab)`` output tile — the working set that made
    very large R overflow VMEM in the untiled kernel no longer scales
    with R. The block axis stays *minor* so, within a slab pass, each
    output tile is still revisited over a contiguous run of blocks (the
    FLYCOO sort-order invariant the accumulation relies on). Cost of
    tiling: the scalar streams (values, local rows) are re-read once per
    slab — ``2·4 B`` per nonzero per slab, negligible against the
    ``(N−1)·R·4 B`` gather traffic each slab pass moves anyway.
    """
    interpret = _resolve_interpret(interpret, blk)
    factor_rows = tuple(factor_rows)
    assert factor_rows, "need at least one input-factor operand"
    n_pad, rank = factor_rows[0].shape
    for fr in factor_rows:
        assert fr.shape == (n_pad, rank), (fr.shape, (n_pad, rank))
    assert n_pad % blk == 0, (n_pad, blk)
    assert rank % rank_slab == 0, (rank, rank_slab)
    assert rows_cap % tile_rows == 0, (rows_cap, tile_rows)
    num_blocks = n_pad // blk
    num_slabs = rank // rank_slab
    n_in = len(factor_rows)

    in_specs = (
        [
            pl.BlockSpec((blk,), lambda s, b, tiles: (b,)),        # local_row
            pl.BlockSpec((blk,), lambda s, b, tiles: (b,)),        # vals
        ]
        + [
            pl.BlockSpec((blk, rank_slab),
                         lambda s, b, tiles: (b, s))               # rows_w
            for _ in range(n_in)
        ]
        + [
            pl.BlockSpec((tile_rows, rank_slab),
                         lambda s, b, tiles: (tiles[b], s)),       # out_init
        ]
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_slabs, num_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile_rows, rank_slab),
                               lambda s, b, tiles: (tiles[b], s)),
    )
    if out_init is None:
        out_init = jnp.zeros((rows_cap, rank), dtype=jnp.float32)
    return pl.pallas_call(
        functools.partial(_fused_nmode_body, tile_rows=tile_rows,
                          block_axis=1),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows_cap, rank), jnp.float32),
        # out_init -> out; operand index counts prefetch + row/val + factors.
        input_output_aliases={3 + n_in: 0},
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(tile_of_block, local_row_in_tile, vals, *factor_rows, out_init)


def _fused_gather_body(*refs, tile_rows: int, onehot_gather: bool,
                       block_axis: int):
    """In-kernel gather + Hadamard + scatter (Alg. 2 lines 13-25 whole).

    Ref layout (positional, after scalar prefetch): ``tile_ref, row_ref,
    val_ref, idx_ref, factors_0 … factors_{K-1}, init_ref, out_ref``.
    Unlike :func:`_fused_nmode_body`, the factor refs here are the
    (replicated, VMEM-resident) factor *matrices*, not pre-gathered row
    blocks: each nonzero's rows are formed by :func:`_gather_rows` on
    its int32 index stream inside the body, so the gathered operands
    never touch HBM. ``onehot_gather`` picks the gather form (one-hot
    MXU matmul on the compiled path, ``jnp.take`` in the interpreter —
    bitwise-identical). The factor refs may be bf16 (bf16-gather
    variants); ``contrib`` starts fp32 so every product accumulates at
    fp32.

    The same body serves the factor-resident and the rank-slabbed
    kernel: the BlockSpecs decide whether a factor ref covers the full
    padded rank or one ``RANK_SLAB`` column slab.
    """
    tile_ref, row_ref, val_ref, idx_ref = refs[0], refs[1], refs[2], refs[3]
    factor_refs = refs[4:-2]
    init_ref, out_ref = refs[-2], refs[-1]
    rows = row_ref[...]
    idx = idx_ref[...]
    contrib = val_ref[...][:, None].astype(jnp.float32)
    for w, f_ref in enumerate(factor_refs):
        contrib = contrib * _gather_rows(f_ref, idx[w],
                                         onehot=onehot_gather,
                                         chunk_rows=GATHER_CHUNK_ROWS)
    update = _scatter_update(rows, contrib, tile_rows)
    _accumulate(tile_ref, init_ref, out_ref, update, block_axis=block_axis)


@functools.partial(
    jax.jit, static_argnames=("rows_cap", "blk", "tile_rows", "interpret")
)
def fused_mttkrp_nmode_gather(
    vals,
    idx_stream,
    factors,
    local_row_in_tile,
    tile_of_block,
    *,
    rows_cap: int,
    blk: int = 512,
    tile_rows: int = 128,
    interpret: bool | None = None,
    out_init=None,
):
    """Factor-resident in-kernel gather variant of the fused kernel.

    Where :func:`fused_mttkrp_nmode` receives N−1 HBM-materialized
    gathered row blocks (``(N−1)·R̂·4`` B written *and* re-read per
    nonzero by the caller), this kernel receives the replicated factor
    matrices whole — held in VMEM across every grid step via a
    constant-index BlockSpec — plus a block-aligned int32 index stream,
    and performs the gather in the body. The per-nonzero HBM stream is
    ``(N−1)·4`` B of indices.

    Args:
      vals: ``(num_blocks*blk,)`` block-aligned nonzero values; padding 0.
      idx_stream: ``(K, num_blocks*blk)`` int32, K = N−1 input modes —
        the factor row index of each nonzero per input mode, in the same
        order as ``factors``; padding slots point at row 0 (harmless:
        their value is 0). Mode-major, so each mode's stream is
        lane-dense: a ``(n, K)`` int32 array would be padded to 128
        lanes on TPU, 512 B per slot.
      factors: tuple/list of K ``(I_pad_w, R)`` replicated input-factor
        matrices (the output mode's factor is *not* passed). R identical
        across operands, a multiple of ``MXU_RANK_MULTIPLE`` (ops.py
        pads). fp32 or bf16 — the Hadamard always accumulates at fp32.
      local_row_in_tile: ``(num_blocks*blk,)`` int32 row within its tile.
      tile_of_block: ``(num_blocks,)`` int32 output tile per block,
        non-decreasing.
      rows_cap: total output rows (multiple of tile_rows).

    Returns:
      ``(rows_cap, R)`` float32 accumulated output.
    """
    interpret = _resolve_interpret(interpret, blk)
    factors = tuple(factors)
    assert factors, "need at least one input-factor matrix"
    n_in, n_pad = idx_stream.shape
    assert n_in == len(factors), (n_in, len(factors))
    rank = factors[0].shape[1]
    for f in factors:
        assert f.shape[1] == rank, (f.shape, rank)
    assert n_pad % blk == 0, (n_pad, blk)
    assert rows_cap % tile_rows == 0, (rows_cap, tile_rows)
    num_blocks = n_pad // blk

    in_specs = (
        [
            pl.BlockSpec((blk,), lambda b, tiles: (b,)),           # local_row
            pl.BlockSpec((blk,), lambda b, tiles: (b,)),           # vals
            pl.BlockSpec((n_in, blk), lambda b, tiles: (0, b)),    # idx stream
        ]
        + [
            # Whole replicated factor matrix, block index pinned at the
            # origin: resident in VMEM for the entire grid sweep.
            pl.BlockSpec(f.shape, lambda b, tiles: (0, 0))
            for f in factors
        ]
        + [
            pl.BlockSpec((tile_rows, rank),
                         lambda b, tiles: (tiles[b], 0)),          # out_init
        ]
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_blocks,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile_rows, rank),
                               lambda b, tiles: (tiles[b], 0)),
    )
    if out_init is None:
        out_init = jnp.zeros((rows_cap, rank), dtype=jnp.float32)
    return pl.pallas_call(
        functools.partial(_fused_gather_body, tile_rows=tile_rows,
                          onehot_gather=not interpret, block_axis=0),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows_cap, rank), jnp.float32),
        # out_init -> out; operand index counts prefetch + row/val/idx +
        # the K factor matrices.
        input_output_aliases={4 + n_in: 0},
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(tile_of_block, local_row_in_tile, vals, idx_stream, *factors, out_init)


@functools.partial(
    jax.jit,
    static_argnames=("rows_cap", "blk", "tile_rows", "rank_slab",
                     "interpret"),
)
def fused_mttkrp_nmode_gather_tiled(
    vals,
    idx_stream,
    factors,
    local_row_in_tile,
    tile_of_block,
    *,
    rows_cap: int,
    blk: int = 512,
    tile_rows: int = 128,
    rank_slab: int = RANK_SLAB,
    interpret: bool | None = None,
    out_init=None,
):
    """Slab-streamed in-kernel gather: one rank slab of each factor resident.

    Same contract as :func:`fused_mttkrp_nmode_gather` with R required to
    be a multiple of ``rank_slab`` (ops.py's ``pad_rank`` guarantees it).
    The grid gains a *major* axis over rank slabs, exactly like
    :func:`fused_mttkrp_nmode_tiled`:

        grid = (R // rank_slab, num_blocks)

    and each factor's BlockSpec selects the slab's column window of the
    matrix, so the resident set per step is ``ΣI_pad·rank_slab·gi``
    instead of ``ΣI_pad·R̂·gi`` — huge ranks no longer force the factors
    out of VMEM. The block axis stays minor (FLYCOO sort-order
    invariant); cost of slabbing: the scalar + index streams are re-read
    once per slab (``(2+K)·4`` B per nonzero per slab), still a factor
    ``R̂/rank_slab`` smaller than streaming pre-gathered rows.
    """
    interpret = _resolve_interpret(interpret, blk)
    factors = tuple(factors)
    assert factors, "need at least one input-factor matrix"
    n_in, n_pad = idx_stream.shape
    assert n_in == len(factors), (n_in, len(factors))
    rank = factors[0].shape[1]
    for f in factors:
        assert f.shape[1] == rank, (f.shape, rank)
    assert n_pad % blk == 0, (n_pad, blk)
    assert rank % rank_slab == 0, (rank, rank_slab)
    assert rows_cap % tile_rows == 0, (rows_cap, tile_rows)
    num_blocks = n_pad // blk
    num_slabs = rank // rank_slab

    in_specs = (
        [
            pl.BlockSpec((blk,), lambda s, b, tiles: (b,)),        # local_row
            pl.BlockSpec((blk,), lambda s, b, tiles: (b,)),        # vals
            pl.BlockSpec((n_in, blk), lambda s, b, tiles: (0, b)),  # idx
        ]
        + [
            # One rank slab of the whole factor matrix per slab pass.
            pl.BlockSpec((f.shape[0], rank_slab),
                         lambda s, b, tiles: (0, s))
            for f in factors
        ]
        + [
            pl.BlockSpec((tile_rows, rank_slab),
                         lambda s, b, tiles: (tiles[b], s)),       # out_init
        ]
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_slabs, num_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile_rows, rank_slab),
                               lambda s, b, tiles: (tiles[b], s)),
    )
    if out_init is None:
        out_init = jnp.zeros((rows_cap, rank), dtype=jnp.float32)
    return pl.pallas_call(
        functools.partial(_fused_gather_body, tile_rows=tile_rows,
                          onehot_gather=not interpret, block_axis=1),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows_cap, rank), jnp.float32),
        # out_init -> out; operand index counts prefetch + row/val/idx +
        # the K factor matrices.
        input_output_aliases={4 + n_in: 0},
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(tile_of_block, local_row_in_tile, vals, idx_stream, *factors, out_init)


def _fused_gather_stream_body(*refs, tile_rows: int, num_in_modes: int,
                              window_tiles: tuple, frow_tile: int,
                              onehot_gather: bool):
    """Out-of-core gather: windowed factor tiles + Hadamard + scatter.

    Ref layout (positional): ``tile_ref, sched_pref_0 … sched_pref_{K-1}``
    (the scalar-prefetched SMEM schedules — consumed both by the
    BlockSpec index maps *and* here), then ``row_ref, val_ref, idx_ref,
    win_{0,0} … win_{K-1,W_{K-1}-1}, init_ref, out_ref``. Each
    ``win_{w,j}`` is one ``(frow_tile, slab)`` VMEM slot whose HBM source
    tile the prefetched schedule selected for this block. The body maps
    each nonzero's global factor row to its window slot by scanning this
    block's schedule row — read scalar-by-scalar from SMEM (a ``(1, W)``
    VMEM copy would violate Mosaic's sublane tiling), the scan unrolled
    over the static window width, reverse order so the *first* matching
    slot wins:

        slot  = first j with  global_row // frow_tile == sched[b, j]
        local = slot · frow_tile + global_row % frow_tile

    The gathered values are bitwise the rows the factor-resident kernel
    would have gathered, so the arithmetic (and its order) is unchanged
    — streamed ≡ resident bit-exactly. Padding/invalid nonzeros may miss
    every scheduled tile (no hit keeps the default slot 0); they then
    gather an arbitrary in-window row, harmless at value 0.
    """
    k = num_in_modes
    sched_refs = refs[1:1 + k]
    row_ref, val_ref, idx_ref = refs[1 + k], refs[2 + k], refs[3 + k]
    win_refs = refs[4 + k:-2]
    init_ref, out_ref = refs[-2], refs[-1]
    b = pl.program_id(1)                     # grid = (slabs, blocks)
    rows = row_ref[...]
    idx = idx_ref[...]
    contrib = val_ref[...][:, None].astype(jnp.float32)
    off = 0
    for w in range(k):
        width = window_tiles[w]
        slots = [win_refs[off + j][...] for j in range(width)]
        off += width
        window = slots[0] if width == 1 else jnp.concatenate(slots, axis=0)
        gtile = (idx[w] // frow_tile).astype(jnp.int32)
        slot = jnp.zeros_like(gtile)
        for j in range(width - 1, -1, -1):
            slot = jnp.where(gtile == sched_refs[w][b, j], j, slot)
        local = slot * frow_tile + idx[w] % frow_tile
        contrib = contrib * _gather_rows(window, local,
                                         onehot=onehot_gather)
    update = _scatter_update(rows, contrib, tile_rows)
    _accumulate(refs[0], init_ref, out_ref, update, block_axis=1)


@functools.partial(
    jax.jit,
    static_argnames=("rows_cap", "blk", "tile_rows", "frow_tile",
                     "rank_slab", "interpret"),
)
def fused_mttkrp_nmode_gather_stream(
    vals,
    idx_stream,
    factors,
    local_row_in_tile,
    tile_of_block,
    tile_schedules,
    *,
    rows_cap: int,
    blk: int = 512,
    tile_rows: int = 128,
    frow_tile: int = FACTOR_ROW_TILE,
    rank_slab: int = RANK_SLAB,
    interpret: bool | None = None,
    out_init=None,
):
    """Out-of-core in-kernel gather: HBM-resident factors, windowed tiles.

    Same contract as :func:`fused_mttkrp_nmode_gather` except the factor
    matrices are **never** held resident: per input mode ``w`` the kernel
    keeps a window of ``W_w = tile_schedules[w].shape[1]`` VMEM slots of
    ``frow_tile`` factor rows each, and the Pallas pipeline DMAs each
    slot's HBM tile per grid step — double-buffered against the previous
    step's compute, exactly like every other streamed operand — with the
    source tile chosen by the scalar-prefetched ``tile_schedules``
    (ops.py's ``_tile_schedule`` builds them from the index stream: the
    sorted distinct ``frow_tile``-row tiles each block touches).
    FLYCOO's row-sorted blocks keep the schedule monotone within a
    block, so when consecutive blocks keep a slot on the same tile the
    pipeline skips the re-fetch.

    Extra preconditions over the resident kernel:
      * each factor's row count is a multiple of ``frow_tile`` (ops.py
        pads);
      * ``tile_schedules[w]`` is ``(num_blocks, W_w)`` int32 with every
        tile of block ``b``'s nonzeros present in row ``b`` — guaranteed
        by construction when ``W_w >= min(blk, ceil(rows_w /
        frow_tile))``, the bound ``repro.oocore.planner`` plans with;
      * R is a multiple of ``rank_slab`` (the stream kernel always
        composes with the rank-slab grid axis — grid =
        ``(R // rank_slab, num_blocks)`` — so the window cost is
        independent of R; pass ``rank_slab=R̂`` to disable slabbing).

    ``out_init`` as in :func:`fused_mttkrp_nmode`: the accumulator the
    output starts from (``None`` = zeros), which lets the chunked
    executor reproduce single-pass accumulation order bit-exactly.

    Returns ``(rows_cap, R)`` float32 accumulated output.
    """
    interpret = _resolve_interpret(interpret, blk)
    factors = tuple(factors)
    tile_schedules = tuple(tile_schedules)
    assert factors, "need at least one input-factor matrix"
    n_in, n_pad = idx_stream.shape
    assert n_in == len(factors) == len(tile_schedules), (
        n_in, len(factors), len(tile_schedules))
    rank = factors[0].shape[1]
    for f in factors:
        assert f.shape[1] == rank, (f.shape, rank)
        assert f.shape[0] % frow_tile == 0, (f.shape, frow_tile)
    assert n_pad % blk == 0, (n_pad, blk)
    assert rank % rank_slab == 0, (rank, rank_slab)
    assert rows_cap % tile_rows == 0, (rows_cap, tile_rows)
    num_blocks = n_pad // blk
    num_slabs = rank // rank_slab
    window_tiles = tuple(s.shape[1] for s in tile_schedules)
    for w, s in enumerate(tile_schedules):
        assert s.shape == (num_blocks, window_tiles[w]), (s.shape, w)

    in_specs = (
        [
            pl.BlockSpec((blk,), lambda s, b, tiles, *scheds: (b,)),
            pl.BlockSpec((blk,), lambda s, b, tiles, *scheds: (b,)),
            pl.BlockSpec((n_in, blk),
                         lambda s, b, tiles, *scheds: (0, b)),
        ]
        + [
            # Window slot j of mode w: one frow_tile-row, rank_slab-wide
            # factor tile, whose source the prefetched schedule picks.
            # The factor itself stays in HBM; only these slots are VMEM.
            pl.BlockSpec((frow_tile, rank_slab),
                         lambda s, b, tiles, *scheds, w=w, j=j:
                         (scheds[w][b, j], s))
            for w in range(n_in) for j in range(window_tiles[w])
        ]
        + [
            pl.BlockSpec((tile_rows, rank_slab),
                         lambda s, b, tiles, *scheds: (tiles[b], s)),
        ]
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1 + n_in,       # tile_of_block + K schedules
        grid=(num_slabs, num_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile_rows, rank_slab),
                               lambda s, b, tiles, *scheds: (tiles[b], s)),
    )
    if out_init is None:
        out_init = jnp.zeros((rows_cap, rank), dtype=jnp.float32)
    window_operands = [factors[w] for w in range(n_in)
                       for _ in range(window_tiles[w])]
    return pl.pallas_call(
        functools.partial(
            _fused_gather_stream_body, tile_rows=tile_rows,
            num_in_modes=n_in, window_tiles=window_tiles,
            frow_tile=frow_tile, onehot_gather=not interpret),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows_cap, rank), jnp.float32),
        # out_init -> out; operand index counts the 1+K prefetch args +
        # row/val/idx + ΣW_w window slots (the body reads the schedules
        # straight from the SMEM prefetch refs — no VMEM copy).
        input_output_aliases={4 + n_in + sum(window_tiles): 0},
        interpret=interpret,
        compiler_params=_compiler_params(),
    )(tile_of_block, *tile_schedules, local_row_in_tile, vals, idx_stream,
      *window_operands, out_init)


def fused_mttkrp_3mode(
    vals,
    rows_a,
    rows_b,
    local_row_in_tile,
    tile_of_block,
    *,
    rows_cap: int,
    blk: int = 512,
    tile_rows: int = 128,
    interpret: bool | None = None,
):
    """Deprecated alias: the 3-mode special case of the N-mode kernel.

    Kept only for pre-N-mode callers; there is one kernel entry per
    family and this is not it. Call :func:`fused_mttkrp_nmode` with
    ``factor_rows=(rows_a, rows_b)`` instead — identical output,
    bitwise.
    """
    import warnings

    warnings.warn(
        "fused_mttkrp_3mode is a deprecated alias; call "
        "fused_mttkrp_nmode(vals, (rows_a, rows_b), ...) instead",
        DeprecationWarning, stacklevel=2)
    return fused_mttkrp_nmode(
        vals, (rows_a, rows_b), local_row_in_tile, tile_of_block,
        rows_cap=rows_cap, blk=blk, tile_rows=tile_rows, interpret=interpret,
    )
